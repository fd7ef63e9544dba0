"""DFabric collectives — the executor that lowers a :class:`CommSchedule`
to JAX ops.

All functions here run *inside* a ``shard_map`` whose manual axes are the
DP domain.  The fast side of the domain is an ORDERED tuple of axes,
fastest first (e.g. ``("data", "host")`` for intra-host ICI then rack-level
CXL); the slowest tier (``slow_axis``, the paper's Ethernet / "pod") is
where the NIC pool stripes.  The TP axis ("model") stays an auto (GSPMD)
axis.

The tier walk itself is NOT encoded here anymore: ``repro.core.schedule``
builds a typed leg list (``ReduceScatter`` / ``Psum`` / ``SlowChunk`` /
``AllGather``) once, and this module only lowers legs:

  * sequential lowering walks the legs in order — reduce-scatter down,
    slow chunks, all-gather up (numerically a flat ``lax.psum`` at every
    depth, codec legs to tolerance);
  * **pipelined** lowering (``CommSchedule.pipelined``) splits the tensor
    into ``chunks`` along the scatter dim and software-pipelines the slow
    leg: chunk *i*'s slow-tier psum is issued while chunk *i−1* runs its
    fast-tier all-gathers (double-buffered — the paper's NIC pool keeping
    the Ethernet leg busy while CXL/ICI do local work).  Same numerics:
    ``psum(x) == concat(psum(chunk_i))`` exactly.

Codec / chunking (``SyncConfig``) apply to the slowest leg — DFabric's
point is that bandwidth is scarce exactly there; an optional ``mid_codec``
compresses mid-tier legs — unscattered psums AND scattered reduce-scatter
legs (fastest active tier stays exact) — in deep hierarchies.  The legacy
entry points (``dfabric_all_reduce`` / ``dfabric_reduce_scatter``, and
``dfabric_all_to_all`` for ``kind="all_to_all"`` schedules — shuffle / MoE
dispatch traffic) survive as thin constructors: given no schedule they
build one in-trace from ``(axes, SyncConfig, shape)`` via the same builder
the planner uses.

Each leg lowers inside a ``jax.named_scope`` named for its kind
(``reduce_scatter``, ``psum``, ``slow_chunk``, ``all_gather``,
``all_to_all``): the compiled ops carry it in their ``op_name``, so a
device trace gives each tier's legs their own time.
"""
from __future__ import annotations

from dataclasses import replace as _dc_replace
from typing import List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import compression as comp
from repro.core.schedule import (AllGather, AllToAll, CommSchedule, Psum,
                                 ReduceScatter, SlowChunk, SyncConfig,
                                 all_to_all_from_axes, build_schedule,
                                 schedule_from_axes)
from repro.utils.jax_compat import axis_size

__all__ = [
    "SyncConfig", "dfabric_all_reduce", "dfabric_reduce_scatter",
    "dfabric_all_gather", "dfabric_all_to_all", "pod_psum",
    "lower_all_reduce", "lower_all_to_all", "lower_reduce_scatter",
    "ring_all_reduce", "normalize_axes", "fast_axes_size", "slow_split",
]

Axes = Union[str, Sequence[str]]


# ---------------------------------------------------------------------------
# Axis helpers
# ---------------------------------------------------------------------------


def normalize_axes(fast_axis: Optional[Axes]) -> Tuple[str, ...]:
    """A single axis name or an ordered sequence -> tuple, fastest first."""
    if fast_axis is None:
        return ()
    if isinstance(fast_axis, str):
        return (fast_axis,)
    return tuple(fast_axis)


def fast_axes_size(fast_axis: Optional[Axes]) -> int:
    n = 1
    for a in normalize_axes(fast_axis):
        n *= axis_size(a)
    return n


def _split_chunks(x: jax.Array, chunks: int) -> Sequence[jax.Array]:
    if chunks <= 1:
        return [x]
    n = x.shape[0]
    assert n % chunks == 0, (n, chunks)
    return list(x.reshape(chunks, n // chunks))


def _trace_schedule(fast: Tuple[str, ...], slow_axis: Optional[str],
                    cfg: SyncConfig, shape: Tuple[int, ...],
                    scatter_dim: int, lane_offset: int = 0,
                    staging: Optional[str] = None) -> CommSchedule:
    """Build a schedule in-trace from live axis sizes (the legacy entry
    points' constructor path).  ``lane_offset`` preserves the planner's
    NIC-pool stagger and ``staging`` its memory-pool placement when the
    planned schedule had to be rebuilt."""
    sizes = {a: axis_size(a) for a in fast}
    if slow_axis is not None:
        sizes[slow_axis] = axis_size(slow_axis)
    s = schedule_from_axes(fast, slow_axis, cfg, shape, scatter_dim, sizes)
    if lane_offset:
        s = s.with_lane_offset(lane_offset)
    if staging is not None:
        s = s.with_staging(staging)
    return s


def _schedule_usable(schedule: Optional[CommSchedule], x: jax.Array,
                     fast: Tuple[str, ...], slow_axis: Optional[str]) -> bool:
    """A planner-built schedule is trusted only when it describes exactly
    this operand (shape) and these mesh axes; otherwise the executor
    rebuilds in-trace (e.g. the non-nested TP path sees model-global
    shapes the planner never planned for)."""
    if schedule is None:
        return False
    if tuple(schedule.shape) != tuple(x.shape):
        return False
    avail = set(fast) | ({slow_axis} if slow_axis else set())
    return set(schedule.axes) <= avail


# ---------------------------------------------------------------------------
# Leg lowering
# ---------------------------------------------------------------------------


def _slow_chunk_psum(leg: SlowChunk, x_flat: jax.Array,
                     ef_flat: Optional[jax.Array], cfg: SyncConfig
                     ) -> Tuple[jax.Array, Optional[jax.Array]]:
    """Lower ONE slow-tier sub-flow (this is the only leg kind where the
    Section codec runs)."""
    with jax.named_scope("slow_chunk"):
        if leg.codec is None:
            return lax.psum(x_flat, leg.axis), ef_flat
        assert leg.codec == cfg.codec, (leg.codec, cfg.codec)
        codec = cfg.make_codec()
        if isinstance(codec, comp.Int8Codec):
            return comp.compressed_psum_int8(x_flat, leg.axis, codec, ef_flat)
        if isinstance(codec, comp.TopKCodec):
            return comp.compressed_psum_topk(x_flat, leg.axis, codec, ef_flat)
        raise ValueError(leg.codec)


def _psum_leg(leg: Psum, x: jax.Array, cfg: SyncConfig) -> jax.Array:
    """Lower one unscattered (mid-tier / flat) psum leg."""
    with jax.named_scope("psum"):
        if leg.codec is None:
            return lax.psum(x, leg.axis)
        # mid-tier codec: int8 without error feedback (EF state belongs to
        # the slow leg; mid tiers trade exactness for bandwidth per the plan)
        assert leg.codec == cfg.mid_codec, (leg.codec, cfg.mid_codec)
        shp = x.shape
        out, _ = comp.compressed_psum_int8(x.reshape(-1), leg.axis,
                                           cfg.make_mid_codec(), None)
        return out.reshape(shp)


def _rs_leg(leg: ReduceScatter, x: jax.Array, dim: int,
            cfg: SyncConfig) -> jax.Array:
    """Lower one fast-tier reduce-scatter leg (scattered mid-tier legs may
    carry the mid codec — int8 without error feedback, like mid psums)."""
    with jax.named_scope("reduce_scatter"):
        if leg.codec is None:
            return lax.psum_scatter(x, leg.axis, scatter_dimension=dim,
                                    tiled=True)
        assert leg.codec == cfg.mid_codec, (leg.codec, cfg.mid_codec)
        return comp.compressed_reduce_scatter_int8(x, leg.axis,
                                                   cfg.make_mid_codec(), dim)


def slow_split(legs: Sequence[SlowChunk], shape: Tuple[int, ...]) -> str:
    """How :func:`_slow_group` cuts a run of slow chunks from an operand of
    ``shape``: ``"rows"`` slices chunk *i* from the leading dim, which holds
    exactly flat chunk *i* in row-major order, so no flattening relayout
    is made; ``"flat"`` splits the flattened operand.  Rows need a
    codec-less run (the codecs work on flat chunks, error feedback too),
    an N-D operand and a leading dim the chunk count divides."""
    if (len(shape) > 1 and shape[0] % len(legs) == 0
            and all(l.codec is None for l in legs)):
        return "rows"
    return "flat"


def _slow_group(legs: Sequence[SlowChunk], x: jax.Array,
                ef: Optional[jax.Array], cfg: SyncConfig
                ) -> Tuple[jax.Array, Optional[jax.Array]]:
    """Sequentially lower a contiguous run of slow chunks over the shard
    (the non-pipelined slow leg), cut as :func:`slow_split` says.

    Legs arrive in ISSUE order (sub-flow indices rotated by the
    schedule's ``lane_offset``); the payload is split and reassembled by
    ``SlowChunk.index``, so the wire order changes but the result never
    does.  Both cuts give chunk *i* the same elements and the same psum,
    so they agree bitwise."""
    C = len(legs)
    if slow_split(legs, x.shape) == "rows":
        blk = x.shape[0] // C
        outs: List = [None] * C
        for leg in legs:
            i = leg.index
            outs[i], _ = _slow_chunk_psum(leg, x[i * blk:(i + 1) * blk],
                                          None, cfg)
        return (jnp.concatenate(outs, axis=0) if C > 1 else outs[0]), ef
    shp = x.shape
    xf = x.reshape(-1)
    ef_f = ef.reshape(-1) if ef is not None else None
    parts = _split_chunks(xf, C)
    ef_parts = _split_chunks(ef_f, C) if ef_f is not None else [None] * C
    outs = [None] * C
    nefs: List = [None] * C
    for leg in legs:
        o, ne = _slow_chunk_psum(leg, parts[leg.index], ef_parts[leg.index],
                                 cfg)
        outs[leg.index] = o
        nefs[leg.index] = ne
    out = jnp.concatenate(outs) if C > 1 else outs[0]
    if ef is not None:
        nef = (jnp.concatenate(nefs) if C > 1 else nefs[0]).reshape(ef.shape)
    else:
        nef = None
    return out.reshape(shp), nef


def _apply_down(legs: Sequence, x: jax.Array, dim: int, cfg: SyncConfig,
                log: Optional[List]) -> jax.Array:
    """Lower the down phase (ReduceScatter / Psum legs), coalescing runs of
    codec-less psums into one ``lax.psum`` call."""
    pend: List[Psum] = []

    def flush():
        nonlocal x
        if pend:
            with jax.named_scope("psum"):
                x = lax.psum(x, tuple(l.axis for l in pend))
            if log is not None:
                log.extend(pend)
            pend.clear()

    for leg in legs:
        if isinstance(leg, Psum) and leg.codec is None:
            pend.append(leg)
            continue
        flush()
        if isinstance(leg, ReduceScatter):
            x = _rs_leg(leg, x, dim, cfg)
        elif isinstance(leg, Psum):
            x = _psum_leg(leg, x, cfg)
        else:
            raise TypeError(leg)
        if log is not None:
            log.append(leg)
    flush()
    return x


def _lower_sequential(schedule: CommSchedule, x: jax.Array,
                      ef: Optional[jax.Array],
                      log: Optional[List], *, gather_up: bool = True
                      ) -> Tuple[jax.Array, Optional[jax.Array]]:
    dim = max(schedule.scatter_dim, 0)
    cfg = schedule.cfg
    x = _apply_down(schedule.down_legs, x, dim, cfg, log)
    slow = schedule.slow_legs
    if slow:
        x, ef = _slow_group(slow, x, ef, cfg)
        if log is not None:
            log.extend(slow)
    if gather_up:
        for leg in schedule.up_legs:
            with jax.named_scope("all_gather"):
                x = lax.all_gather(x, leg.axis, axis=dim, tiled=True)
            if log is not None:
                log.append(leg)
    return x, ef


def _lower_pipelined(schedule: CommSchedule, x: jax.Array,
                     ef: Optional[jax.Array],
                     log: Optional[List]
                     ) -> Tuple[jax.Array, Optional[jax.Array]]:
    """The overlapped slow-leg pipeline.

    The tensor is split into ``chunks`` along the scatter dim BEFORE the
    fast-tier reduce-scatters (``psum(x) == concat_i(psum(chunk_i))``
    exactly, so numerics are unchanged at every depth / chunk count).  The
    loop is software-pipelined and double-buffered: chunk *i*'s slow-tier
    psum is issued first, THEN chunk *i−1* runs its fast-tier all-gathers,
    so XLA's async scheduler can keep the slow leg busy while the fast
    tiers gather — exactly the overlap ``CostModel.from_schedule`` credits
    (``max(slow, fast) + min(per-chunk)``).

    Error-feedback state pairs local EF slice *i* with chunk *i*; the
    pairing is arbitrary but deterministic, which is all EF needs (each
    member re-consumes the residual of what it compressed last step).
    """
    dim = schedule.scatter_dim
    cfg = schedule.cfg
    C = schedule.chunks
    down, slow, up = schedule.down_legs, schedule.slow_legs, schedule.up_legs
    assert len(slow) == C, (len(slow), C)
    blk = x.shape[dim] // C
    parts = [lax.slice_in_dim(x, i * blk, (i + 1) * blk, axis=dim)
             for i in range(C)]
    if ef is not None:
        ef_f = ef.reshape(-1)
        m = ef_f.shape[0] // C
        ef_parts = [ef_f[i * m:(i + 1) * m] for i in range(C)]
    else:
        ef_parts = [None] * C

    down_log: List = [] if log is not None else None
    slow_log: List = [] if log is not None else None
    up_log: List = [] if log is not None else None

    shards = [_apply_down(down, p, dim, cfg,
                          down_log if i == 0 else None)
              for i, p in enumerate(parts)]
    shard_shape = shards[0].shape

    def issue_slow(pos: int):
        # legs are in ISSUE order; the leg's index picks the data chunk
        # (lane_offset rotation — see CommSchedule.with_lane_offset)
        leg = slow[pos]
        o, ne = _slow_chunk_psum(leg, shards[leg.index].reshape(-1),
                                 ef_parts[leg.index], cfg)
        if slow_log is not None:
            slow_log.append(leg)
        return leg.index, o, ne

    def gather(buf: jax.Array, lg) -> jax.Array:
        y = buf.reshape(shard_shape)
        for leg in up:
            with jax.named_scope("all_gather"):
                y = lax.all_gather(y, leg.axis, axis=dim, tiled=True)
            if lg is not None:
                lg.append(leg)
        return y

    outs: List[Optional[jax.Array]] = [None] * C
    nefs: List[Optional[jax.Array]] = [None] * C
    inflight = issue_slow(0)
    for pos in range(1, C):
        nxt = issue_slow(pos)        # this sub-flow crosses the slow tier
        idx, buf, buf_ef = inflight  # ... while the previous one gathers
        outs[idx] = gather(buf, up_log if pos == 1 else None)
        nefs[idx] = buf_ef
        inflight = nxt
    idx, buf, buf_ef = inflight
    outs[idx] = gather(buf, up_log if C == 1 else None)
    nefs[idx] = buf_ef

    if log is not None:
        log.extend(down_log + slow_log + up_log)
    out = jnp.concatenate(outs, axis=dim)
    nef = None
    if ef is not None:
        nef = jnp.concatenate([e for e in nefs]).reshape(ef.shape)
    return out, nef


def lower_all_reduce(schedule: CommSchedule, x: jax.Array,
                     ef: Optional[jax.Array] = None,
                     leg_log: Optional[List] = None
                     ) -> Tuple[jax.Array, Optional[jax.Array]]:
    """Lower a full all-reduce schedule to JAX ops.

    ``leg_log``, when given, receives the legs actually lowered, in
    schedule order — the acceptance contract is that it equals the leg
    list ``CostModel.from_schedule`` prices."""
    if schedule.kind != "all_reduce":
        raise ValueError(
            f"lower_all_reduce needs an all_reduce schedule, got "
            f"kind={schedule.kind!r} (use lower_all_to_all)")
    if not schedule.legs:
        return x, ef
    if schedule.pipelined and schedule.chunks > 1:
        return _lower_pipelined(schedule, x, ef, leg_log)
    return _lower_sequential(schedule, x, ef, leg_log)


def lower_reduce_scatter(schedule: CommSchedule, x: jax.Array,
                         ef: Optional[jax.Array] = None,
                         leg_log: Optional[List] = None
                         ) -> Tuple[jax.Array, Optional[jax.Array]]:
    """Lower only the down half of a schedule (fast-tier reduce-scatters +
    slow leg), leaving the caller owning its 1/prod(fast sizes) shard —
    the ZeRO-1 entry point (the up legs later carry updated parameters)."""
    assert schedule.strategy == "hier_striped", schedule.strategy
    assert not any(isinstance(l, Psum) for l in schedule.down_legs), \
        "ZeRO-1 sections must scatter every fast tier"
    return _lower_sequential(schedule, x, ef, leg_log, gather_up=False)


# ---------------------------------------------------------------------------
# Legacy entry points — thin constructors over the IR
# ---------------------------------------------------------------------------


def pod_psum(x: jax.Array, slow_axis: Optional[str], cfg: SyncConfig,
             ef: Optional[jax.Array] = None,
             lane_offset: int = 0
             ) -> Tuple[jax.Array, Optional[jax.Array]]:
    """All-reduce ``x`` (this chip's fast-tier-scattered shard) over the
    slowest axis — the bare NIC-pool leg, kept for direct callers.

    ``cfg.chunks`` splits the transfer into independent sub-flows; the
    codec (if any) runs here and only here.  ``lane_offset`` rotates the
    sub-flow issue order (the NIC-pool stagger)."""
    if slow_axis is None or axis_size(slow_axis) == 1:
        return x, ef
    n = axis_size(slow_axis)
    chunks = max(cfg.chunks, 1) if cfg.codec != "topk" else 1
    while chunks > 1 and x.shape[0] % chunks != 0:
        chunks -= 1
    legs = [SlowChunk((j + lane_offset) % chunks, chunks, cfg.codec,
                      slow_axis, slow_axis, n) for j in range(chunks)]
    return _slow_group(legs, x, ef, cfg)


def dfabric_all_reduce(x: jax.Array, fast_axis: Optional[Axes],
                       slow_axis: Optional[str],
                       cfg: SyncConfig, scatter_dim: int = 0,
                       ef: Optional[jax.Array] = None,
                       schedule: Optional[CommSchedule] = None,
                       leg_log: Optional[List] = None,
                       lane_offset: int = 0,
                       staging: Optional[str] = None,
                       ) -> Tuple[jax.Array, Optional[jax.Array]]:
    """All-reduce ``x`` over (fast tiers x slow tier) with the DFabric plan.

    ``fast_axis``: one axis name or an ordered sequence (fastest first).
    ``x`` may be any rank; ``scatter_dim`` is the dimension scattered over
    the fast tiers (must be divisible by the product of the scattered tier
    sizes — indivisible tensors fall back to a flat psum).  When the
    planner already built a :class:`CommSchedule` for this Section, pass
    it via ``schedule``; otherwise one is built in-trace from ``cfg``
    (``lane_offset`` keeps the planner's NIC-pool stagger and ``staging``
    its memory-pool placement on that path — staging is an annotation
    here: the lowering is placement-free on this backend, but the rebuilt
    schedule must round-trip what the planner chose)."""
    fast = normalize_axes(fast_axis)
    if not _schedule_usable(schedule, x, fast, slow_axis):
        schedule = _trace_schedule(fast, slow_axis, cfg, x.shape, scatter_dim,
                                   lane_offset, staging)
    return lower_all_reduce(schedule, x, ef=ef, leg_log=leg_log)


def dfabric_reduce_scatter(x: jax.Array, fast_axis: Axes,
                           slow_axis: Optional[str],
                           cfg: SyncConfig, scatter_dim: int = 0,
                           ef: Optional[jax.Array] = None,
                           schedule: Optional[CommSchedule] = None,
                           leg_log: Optional[List] = None,
                           lane_offset: int = 0,
                           staging: Optional[str] = None):
    """Like :func:`dfabric_all_reduce` but stops before the final fast-tier
    all-gathers — the caller owns the 1/prod(fast sizes) shard, indexed
    fastest-tier-major (ZeRO-1 entry point)."""
    fast = normalize_axes(fast_axis)
    nf = fast_axes_size(fast)
    assert x.shape[scatter_dim] % nf == 0, (x.shape, scatter_dim, nf)
    if not _schedule_usable(schedule, x, fast, slow_axis) \
            or schedule.strategy != "hier_striped" \
            or any(isinstance(l, Psum) for l in schedule.down_legs):
        full = _dc_replace(cfg, scatter_depth=-1)
        schedule = _trace_schedule(fast, slow_axis, full, x.shape,
                                   scatter_dim, lane_offset, staging)
    return lower_reduce_scatter(schedule, x, ef=ef, leg_log=leg_log)


def dfabric_all_gather(x: jax.Array, fast_axis: Axes,
                       gather_dim: int = 0) -> jax.Array:
    """All-gather over the fast tiers, undoing
    :func:`dfabric_reduce_scatter`'s ownership order (gathers run in
    reverse tier order so the fastest tier ends up major)."""
    fast = normalize_axes(fast_axis)
    for a in reversed(fast):
        if axis_size(a) > 1:
            with jax.named_scope("all_gather"):
                x = lax.all_gather(x, a, axis=gather_dim, tiled=True)
    return x


# ---------------------------------------------------------------------------
# Multi-stage hierarchical all-to-all (the NIC pool applied to MoE dispatch /
# shuffle traffic, paper §6.2 WordCount + our §Perf cell C)
# ---------------------------------------------------------------------------


def lower_all_to_all(schedule: CommSchedule, x: jax.Array,
                     leg_log: Optional[List] = None) -> jax.Array:
    """Lower a ``kind="all_to_all"`` schedule to JAX ops.

    ``x``: (n_total, ...) — row r holds the payload for member r of the
    DP domain, rows ordered slow-major (slowest tier's sub-index is the
    most significant digit, the fastest tier's the least).  A flat
    all-to-all would move every cross-group row point-to-point over the
    slow tier; the hierarchical form exchanges each tier's OWN sub-index
    starting from the fastest tier, so that by the time a stripe crosses
    a slow tier it is a single contiguous block and every member of the
    faster tiers below carries exactly its 1/members_below share of the
    cross-tier traffic (the pool).  Numerically equivalent to
    ``lax.all_to_all(x, (slowest, ..., fastest), 0, 0)`` at every depth.

    The slow tier's exchange runs as the schedule's ``SlowChunk``
    sub-flows: each sub-flow exchanges an equal slice of every
    destination's payload, issued in leg order (``lane_offset`` rotation)
    and reassembled by ``SlowChunk.index`` — bitwise identical at every
    chunk count and offset, since an all-to-all restricted to a payload
    slice is the same block permutation.  ``leg_log`` receives the legs
    actually lowered, in schedule order (the battery's contract with
    ``CostModel.from_schedule``)."""
    if schedule.kind != "all_to_all":
        raise ValueError(
            f"lower_all_to_all needs an all_to_all schedule, got "
            f"kind={schedule.kind!r}")
    fast_legs = [l for l in schedule.legs if isinstance(l, AllToAll)]
    slow = schedule.slow_legs
    active = [(l.axis, l.size) for l in fast_legs]
    if slow:
        active.append((slow[0].axis, slow[0].size))
    if not active:
        return x
    sizes = [n for _, n in active]
    n_total = 1
    for n in sizes:
        n_total *= n
    assert x.shape[0] == n_total, (x.shape, sizes)
    rest = x.shape[1:]
    # leading dim viewed slow-major: dims ordered (slowest, ..., fastest)
    y = x.reshape(tuple(reversed(sizes)) + rest)
    k = len(active)
    for i, leg in enumerate(fast_legs):  # fastest tier first
        d = k - 1 - i  # its sub-index dim in the slow-major view
        with jax.named_scope("all_to_all"):
            y = lax.all_to_all(y, leg.axis, split_axis=d, concat_axis=d,
                               tiled=True)
        if leg_log is not None:
            leg_log.append(leg)
    if slow:
        C = len(slow)
        n_slow = slow[0].size
        yshape = y.shape
        yf = y.reshape(n_slow, -1)
        blk = yf.shape[1] // C
        outs: List[Optional[jax.Array]] = [None] * C
        for leg in slow:  # ISSUE order; payload slice picked by index
            part = lax.slice_in_dim(yf, leg.index * blk,
                                    (leg.index + 1) * blk, axis=1)
            with jax.named_scope("slow_chunk"):
                outs[leg.index] = lax.all_to_all(part, leg.axis, split_axis=0,
                                                 concat_axis=0, tiled=True)
            if leg_log is not None:
                leg_log.append(leg)
        yf = jnp.concatenate(outs, axis=1) if C > 1 else outs[0]
        y = yf.reshape(yshape)
    return y.reshape((n_total,) + rest)


def dfabric_all_to_all(x: jax.Array, fast_axis: Axes,
                       slow_axis: Optional[str],
                       cfg: Optional[SyncConfig] = None,
                       schedule: Optional[CommSchedule] = None,
                       leg_log: Optional[List] = None,
                       lane_offset: int = 0,
                       staging: Optional[str] = None) -> jax.Array:
    """All-to-all over the (fast tiers x slow tier) DP domain, one stage
    per tier — the thin in-trace constructor over
    :func:`lower_all_to_all` (see its docstring for the payload layout
    and numerics contract).

    When the planner already built a ``kind="all_to_all"``
    :class:`CommSchedule` for this exchange (``Planner.plan_all_to_all``),
    pass it via ``schedule``; otherwise one is built in-trace from
    ``cfg`` (default: one slow sub-flow) and the live axis sizes —
    ``lane_offset`` keeps the planner's NIC-pool stagger and ``staging``
    its memory-pool placement on that path, exactly like
    :func:`dfabric_all_reduce`."""
    if schedule is not None and schedule.kind != "all_to_all":
        raise ValueError(
            f"dfabric_all_to_all needs an all_to_all schedule, got "
            f"kind={schedule.kind!r}")
    fast = normalize_axes(fast_axis)
    if not _schedule_usable(schedule, x, fast, slow_axis):
        cfg = cfg or SyncConfig()
        sizes = {a: axis_size(a) for a in fast}
        if slow_axis is not None:
            sizes[slow_axis] = axis_size(slow_axis)
        schedule = all_to_all_from_axes(fast, slow_axis, cfg, x.shape, sizes)
        if lane_offset:
            schedule = schedule.with_lane_offset(lane_offset)
        if staging is not None:
            schedule = schedule.with_staging(staging)
    return lower_all_to_all(schedule, x, leg_log=leg_log)


# ---------------------------------------------------------------------------
# Explicit ring all-reduce via ppermute (used for >2 pods and in tests;
# also the reference implementation of the paper's ring-Allreduce figure)
# ---------------------------------------------------------------------------


def ring_all_reduce(x: jax.Array, axis_name: str, n: int) -> jax.Array:
    """Bandwidth-optimal ring all-reduce implemented with ppermute.

    ``n`` must be the static size of ``axis_name``; ``x.shape[0]`` must be
    divisible by ``n``.  Matches ``lax.psum`` numerically (up to fp
    reassociation).
    """
    if n == 1:
        return x
    assert x.shape[0] % n == 0, (x.shape, n)
    chunks = x.reshape(n, -1)
    idx = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]

    # reduce-scatter phase: after n-1 steps, rank i owns fully-reduced
    # chunk (i+1) % n.
    def send_chunk(c, k):
        # chunk index this rank sends at step k: (idx - k) mod n
        j = jnp.mod(idx - k, n)
        return jnp.take(c, j, axis=0), j

    acc = chunks
    buf, j = send_chunk(acc, 0)
    for k in range(n - 1):
        recv = lax.ppermute(buf, axis_name, perm)
        jr = jnp.mod(idx - k - 1, n)
        acc = acc.at[jr].add(recv)
        if k < n - 2:
            buf = jnp.take(acc, jr, axis=0)
    # all-gather phase
    own = jnp.mod(idx + 1, n)
    buf = jnp.take(acc, own, axis=0)
    out = acc
    for k in range(n - 1):
        recv = lax.ppermute(buf, axis_name, perm)
        jr = jnp.mod(own - k - 1, n)
        out = out.at[jr].set(recv)
        buf = recv
    return out.reshape(x.shape)
