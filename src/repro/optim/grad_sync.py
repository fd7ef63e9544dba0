"""DFabric gradient synchronization — the paper's DDP port, plus ZeRO-1.

This module executes a :class:`repro.core.planner.SyncPlan` inside a
``shard_map`` whose manual axes are the DP domain; when the mesh has a
TP axis ("model") the train step nests it in a second, model-manual
``shard_map``, so every operand has the per-TP-shard shape the planner
planned.  Each Section carries the planner-built
:class:`~repro.core.schedule.CommSchedule`, which is handed to the
executor (``collectives.dfabric_reduce_scatter`` /
``dfabric_all_reduce``) and lowered as it is: no tier plan is re-derived
here.  The fast side of the domain is an ORDERED tuple of tiers
(``SyncSettings.fast_axes``, fastest first — e.g. ``("data", "host")``
for intra-host ICI then rack-level CXL); the slowest tier (``slow_axis``
== "pod", the DCN / Ethernet leg) is where the NIC pool stripes.

Two modes:

  * ``paper``  — faithful DFabric DDP: every gradient Section is
    all-reduced with the hierarchical striped collective (reduce-scatter
    over ICI -> NIC-pool striped pod all-reduce -> all-gather over ICI),
    then a replicated AdamW update runs.
  * ``zero1``  — beyond-paper fusion: the sync *stops at the shard* after
    the pod leg, AdamW updates the 1/N_ici parameter shard with optimizer
    moments that live sharded over the ICI axis (the "memory pool" holding
    state at aggregate-HBM capacity), and the final ICI all-gather carries
    *updated parameters* instead of gradients — one full ICI pass saved
    per step, and 16x less optimizer memory per chip.

Optional DCN compression (int8 + error feedback / top-k) applies only to
the slow tier, where DFabric says bandwidth is scarce.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core.collectives import (dfabric_all_gather, dfabric_all_reduce,
                                    dfabric_reduce_scatter, slow_split)
from repro.utils.jax_compat import axis_size
from repro.core.planner import Section, SyncPlan
from repro.optim.adamw import AdamWConfig, adamw_leaf
from repro.utils.trees import tree_from_paths, tree_paths


# ---------------------------------------------------------------------------
# Section <-> tensors packing
# ---------------------------------------------------------------------------


def _bucket_pack(flat: Dict[str, jax.Array], sec: Section, n_fast: int) -> jax.Array:
    parts = [flat[p].reshape(-1).astype(jnp.float32) for p in sec.leaf_paths]
    x = jnp.concatenate(parts) if len(parts) > 1 else parts[0]
    pad = (-x.shape[0]) % n_fast
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad,), x.dtype)])
    return x


def _bucket_unpack(x: jax.Array, sec: Section,
                   templates: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    out = {}
    off = 0
    for p in sec.leaf_paths:
        t = templates[p]
        n = int(np.prod(t.shape))
        out[p] = x[off:off + n].reshape(t.shape).astype(t.dtype)
        off += n
    return out


def bucket_padded_numel(sec: Section, n_fast: int) -> int:
    return sec.numel + ((-sec.numel) % n_fast)


# ---------------------------------------------------------------------------
# Optimizer-state construction (global shapes + shard_map specs)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class SyncSettings:
    """DP-domain axis layout of one sync plan.

    ``fast_axes`` is the ordered fast-tier axis list (fastest first).
    ``n_fast`` is the PRODUCT of all fast-tier sizes (ZeRO-1 shards are
    1/n_fast)."""

    mode: str = "zero1"  # "paper" | "zero1"
    fast_axes: Tuple[str, ...] = ("data",)  # ordered, fastest first
    slow_axis: Optional[str] = "pod"
    n_fast: int = 1
    n_slow: int = 1
    # set when sync_and_update runs inside the nested model-manual
    # shard_map (§Perf iteration 6): TP-sharded sections then psum their
    # sq-norms over this axis too
    model_axis: Optional[str] = None

    @property
    def fast_entry(self):
        """PartitionSpec entry for a dim scattered over the fast tiers:
        the bare axis name for one tier, the ordered tuple for several
        (fastest-major, matching dfabric_reduce_scatter ownership)."""
        f = self.fast_axes
        return f if len(f) > 1 else f[0]

    @property
    def dp_total(self) -> int:
        return self.n_fast * self.n_slow


def flat_fast_index(ss: SyncSettings):
    """This rank's flattened index over the fast tiers, fastest-tier-major
    (matches the ownership order of ``dfabric_reduce_scatter``)."""
    idx = None
    for a in ss.fast_axes:
        i = lax.axis_index(a)
        idx = i if idx is None else idx * axis_size(a) + i
    return idx if idx is not None else jnp.int32(0)


def full_depth(sec: Section, ss: SyncSettings) -> bool:
    """The ZeRO-1 fused path owns a 1/n_fast shard, which requires the
    section's tier plan to scatter over EVERY fast tier."""
    d = sec.sync.scatter_depth
    return d < 0 or d >= len(ss.fast_axes)


def section_kind(sec: Section, ss: SyncSettings) -> str:
    """'shard' (fused ZeRO-1 path), 'full_tensor' (whole-tensor all-reduce +
    replicated update) or 'bucket' (flat pack of small TP-replicated
    leaves)."""
    if len(sec.leaf_paths) > 1:
        return "bucket"
    if ss.mode == "zero1" and sec.sync.strategy == "hier_striped" \
            and sec.scatter_dim >= 0 and full_depth(sec, ss):
        return "shard"
    return "full_tensor"


def zero1_path(sec: Section, ss: SyncSettings) -> bool:
    """The section syncs by reduce-scatter to a shard-resident ZeRO-1
    update (a tensor shard or a scattered flat bucket)."""
    return ss.mode == "zero1" and sec.sync.strategy == "hier_striped" \
        and (len(sec.leaf_paths) > 1
             or (sec.scatter_dim >= 0 and full_depth(sec, ss)))


def slow_split_counts(plan: SyncPlan, ss: SyncSettings) -> Dict[str, int]:
    """Slow legs of ``plan`` that the sequential slow leg cuts by rows and
    by flat chunks (``collectives.slow_split`` on the shard each planned
    schedule hands it), read from the plan alone.  Legs of a pipelined
    all-reduce never reach that leg and are not counted."""
    counts = {"rows": 0, "flat": 0}
    for sec in plan.sections:
        s = sec.schedule
        if s is None or not s.slow_legs:
            continue
        if not zero1_path(sec, ss) and s.pipelined and s.chunks > 1:
            continue
        shape = list(s.shape)
        shape[max(s.scatter_dim, 0)] //= s.scattered_prod
        counts[slow_split(s.slow_legs, tuple(shape))] += len(s.slow_legs)
    return counts


def init_sync_state(plan: SyncPlan, param_shapes: Dict[str, Any],
                    ss: SyncSettings) -> Dict[str, Any]:
    """Global-shaped optimizer state: moments per Section (+EF when the
    Section uses a codec).  In zero1 mode these arrays are *sharded over
    the ICI axis* via :func:`sync_state_specs`."""
    flat = tree_paths(param_shapes)
    state: Dict[str, Any] = {"step": jnp.zeros((), jnp.int32), "sections": {}}
    for sec in plan.sections:
        if section_kind(sec, ss) == "bucket":
            shape = (bucket_padded_numel(sec, ss.n_fast),)
        else:
            shape = tuple(flat[sec.leaf_paths[0]].shape)
        entry = {"m": jnp.zeros(shape, jnp.float32),
                 "v": jnp.zeros(shape, jnp.float32)}
        if sec.sync.codec is not None and sec.sync.error_feedback:
            entry["ef"] = jnp.zeros(shape, jnp.float32)
        state["sections"][sec.name] = entry
    return state


def sync_state_specs(plan: SyncPlan, param_shapes: Dict[str, Any],
                     ss: SyncSettings) -> Dict[str, Any]:
    """shard_map PartitionSpecs for the sync state (manual axes only)."""
    flat = tree_paths(param_shapes)
    specs: Dict[str, Any] = {"step": P(), "sections": {}}
    for sec in plan.sections:
        kind = section_kind(sec, ss)

        def shard_spec() -> P:
            if kind == "shard":
                nd = len(flat[sec.leaf_paths[0]].shape)
                sp = [None] * nd
                sp[sec.scatter_dim] = ss.fast_entry
                return P(*sp)
            if kind == "bucket" and sec.sync.strategy == "hier_striped":
                return P(ss.fast_entry)
            return P()

        # moments are shard-resident on the fused ZeRO-1 paths (tensor shard
        # or scattered flat bucket)
        zero1 = zero1_path(sec, ss)
        mv = shard_spec() if zero1 else P()
        if kind == "bucket" and zero1:
            mv = P(ss.fast_entry)
        entry = {"m": mv, "v": mv}
        if init_entry_has_ef(sec):
            # EF feeds the slow leg, which operates on the shard scattered
            # over the section's fast-tier PREFIX (its scatter_depth)
            scattered = _scattered_axes(sec, ss)
            if sec.sync.strategy != "hier_striped":
                entry["ef"] = P()
            elif kind == "bucket":
                entry["ef"] = P(ss.fast_entry)
            elif sec.scatter_dim >= 0 and scattered:
                nd = len(flat[sec.leaf_paths[0]].shape)
                sp = [None] * nd
                sp[sec.scatter_dim] = scattered if len(scattered) > 1 else scattered[0]
                entry["ef"] = P(*sp)
            else:
                entry["ef"] = P()
        specs["sections"][sec.name] = entry
    return specs


def init_entry_has_ef(sec: Section) -> bool:
    return sec.sync.codec is not None and sec.sync.error_feedback


def _scattered_axes(sec: Section, ss: SyncSettings) -> Tuple[str, ...]:
    """The fast-tier axes a hier_striped section actually scatters over —
    the first ``scatter_depth`` entries of the ordered fast-axis list."""
    if sec.sync.strategy != "hier_striped" or sec.scatter_dim < 0:
        return ()
    d = sec.sync.scatter_depth
    return ss.fast_axes if d < 0 else ss.fast_axes[:d]


def inner_state_specs(plan: SyncPlan, param_specs_flat: Dict[str, P],
                      param_shapes_flat: Dict[str, Any]) -> Dict[str, Any]:
    """Model-axis PartitionSpecs for the sync state, used as in/out specs of
    the nested model-manual shard_map.  Single-tensor sections inherit the
    param's TP spec; buckets hold TP-replicated leaves (flat P())."""
    specs: Dict[str, Any] = {"step": P(), "sections": {}}
    for sec in plan.sections:
        if len(sec.leaf_paths) == 1:
            pspec = param_specs_flat[sec.leaf_paths[0]]
            nd = len(param_shapes_flat[sec.leaf_paths[0]].shape)
            sp = P(*(list(pspec) + [None] * (nd - len(pspec))))
        else:
            sp = P()  # buckets hold only TP-replicated leaves
        entry = {"m": sp, "v": sp}
        if init_entry_has_ef(sec):
            entry["ef"] = sp
        specs["sections"][sec.name] = entry
    return specs


def merge_specs(a: P, b: P, ndim: int) -> P:
    """Entry-wise union of two PartitionSpecs (disjoint dims)."""
    ea = list(a) + [None] * (ndim - len(a))
    eb = list(b) + [None] * (ndim - len(b))
    out = []
    for x, y in zip(ea, eb):
        if x is not None and y is not None:
            xs = x if isinstance(x, tuple) else (x,)
            ys = y if isinstance(y, tuple) else (y,)
            out.append(tuple(xs) + tuple(ys))
        else:
            out.append(x if x is not None else y)
    return P(*out)


def merged_state_specs(plan: SyncPlan, param_shapes: Dict[str, Any],
                       param_specs_tree, ss: SyncSettings) -> Dict[str, Any]:
    """Full array shardings for the sync state: manual (data@scatter_dim)
    merged with the param's TP spec — what device_put / the dry-run use."""
    outer = sync_state_specs(plan, param_shapes, ss)
    pflat = tree_paths(param_specs_tree)
    shapes = tree_paths(param_shapes)
    inner = inner_state_specs(plan, pflat, shapes)
    merged: Dict[str, Any] = {"step": P(), "sections": {}}
    for sec in plan.sections:
        o = outer["sections"][sec.name]
        i = inner["sections"][sec.name]
        if len(sec.leaf_paths) == 1:
            nd = len(shapes[sec.leaf_paths[0]].shape)
        else:
            nd = 1
        merged["sections"][sec.name] = {
            k: merge_specs(o[k], i[k], nd) for k in o}
    return merged


# ---------------------------------------------------------------------------
# The sync + update pass (runs INSIDE shard_map over manual DP axes)
# ---------------------------------------------------------------------------


def sync_and_update(params, grads, sync_state, plan: SyncPlan,
                    ss: SyncSettings, lr, opt_cfg: AdamWConfig,
                    fast_idx=None
                    ) -> Tuple[Any, Any, Dict[str, jax.Array]]:
    """Execute the plan; returns (new_params, new_sync_state, metrics).

    ``fast_idx``: this rank's flattened index over the fast tiers.  Must be
    computed *outside* when running inside the nested model-manual
    shard_map (axis_index of a parent-manual axis is not allowed there).
    """
    pflat = tree_paths(params)
    gflat = tree_paths(grads)
    step = sync_state["step"]
    n_fast = ss.n_fast
    inv_dp = 1.0 / ss.dp_total

    # ---- pass 1: communicate ------------------------------------------------
    # device-time scopes (op_name metadata, no runtime cost): "grad_sync"
    # holds packing, collectives, scaling and the norm psums, "adamw" the
    # update and its clip; the two never nest
    synced: Dict[str, Any] = {}
    new_sections: Dict[str, Any] = {}
    sqnorm = jnp.zeros((), jnp.float32)
    for sec in plan.sections:
        entry = dict(sync_state["sections"][sec.name])
        ef = entry.get("ef")
        bucket = len(sec.leaf_paths) > 1
        zero1 = zero1_path(sec, ss)
        model_axes = ((ss.model_axis,) if (ss.model_axis and sec.model_sharded)
                      else ())
        with jax.named_scope("grad_sync"):
            if bucket:
                g = _bucket_pack(gflat, sec, n_fast)
                k = 0
            else:
                g = gflat[sec.leaf_paths[0]].astype(jnp.float32)
                k = max(sec.scatter_dim, 0)
            if zero1:
                shard, new_ef = dfabric_reduce_scatter(
                    g, ss.fast_axes, ss.slow_axis, sec.sync, scatter_dim=k,
                    ef=ef, schedule=sec.schedule)
                shard = shard * inv_dp
                synced[sec.name] = ("shard", shard, k)
                sqnorm = sqnorm + lax.psum(jnp.sum(jnp.square(shard)),
                                           ss.fast_axes + model_axes)
            else:
                full, new_ef = dfabric_all_reduce(
                    g, ss.fast_axes, ss.slow_axis, sec.sync, scatter_dim=k,
                    ef=ef, schedule=sec.schedule)
                full = full * inv_dp
                synced[sec.name] = ("full", full, k)
                sq = jnp.sum(jnp.square(full))
                if model_axes:
                    sq = lax.psum(sq, model_axes)
                sqnorm = sqnorm + sq
        if new_ef is not None:
            entry["ef"] = new_ef
        new_sections[sec.name] = entry

    with jax.named_scope("adamw"):
        gnorm = jnp.sqrt(sqnorm)
        clip = jnp.minimum(1.0, opt_cfg.grad_clip / jnp.maximum(gnorm, 1e-9)) \
            if opt_cfg.grad_clip > 0 else jnp.float32(1.0)

    # ---- pass 2: update -----------------------------------------------------
    new_flat: Dict[str, jax.Array] = {}
    for sec in plan.sections:
        kind, g, k = synced[sec.name]
        entry = new_sections[sec.name]
        bucket = len(sec.leaf_paths) > 1
        if kind == "shard":
            # parameter shard owned by this fast-tier rank (flattened
            # fastest-tier-major over all fast axes)
            idx = fast_idx if fast_idx is not None else flat_fast_index(ss)
            with jax.named_scope("grad_sync"):
                if bucket:
                    p_full = _bucket_pack(pflat, sec, n_fast)
                    blk = p_full.shape[0] // n_fast
                    p_sh = lax.dynamic_slice_in_dim(p_full, idx * blk, blk, axis=0)
                else:
                    p = pflat[sec.leaf_paths[0]]
                    blk = p.shape[k] // n_fast
                    p_sh = lax.dynamic_slice_in_dim(p, idx * blk, blk, axis=k)
            new_p_sh, m, v = adamw_leaf(p_sh, g, entry["m"], entry["v"], step,
                                        lr, opt_cfg, clip)
            entry["m"], entry["v"] = m, v
            # the all-gather now carries UPDATED PARAMETERS (fused ZeRO-1);
            # gathers run up the fast tiers in reverse scatter order
            with jax.named_scope("grad_sync"):
                gathered = dfabric_all_gather(new_p_sh, ss.fast_axes,
                                              gather_dim=(0 if bucket else k))
                if bucket:
                    new_flat.update(_bucket_unpack(gathered, sec, pflat))
                else:
                    new_flat[sec.leaf_paths[0]] = gathered
        elif bucket:
            with jax.named_scope("grad_sync"):
                p_full = _bucket_pack(pflat, sec, n_fast)
            new_p, m, v = adamw_leaf(p_full, g, entry["m"], entry["v"],
                                     step, lr, opt_cfg, clip)
            entry["m"], entry["v"] = m, v
            with jax.named_scope("grad_sync"):
                new_flat.update(_bucket_unpack(new_p, sec, pflat))
        else:
            p = pflat[sec.leaf_paths[0]]
            new_p, m, v = adamw_leaf(p, g, entry["m"], entry["v"], step,
                                     lr, opt_cfg, clip)
            entry["m"], entry["v"] = m, v
            new_flat[sec.leaf_paths[0]] = new_p
        new_sections[sec.name] = entry

    new_params = tree_from_paths({**pflat, **new_flat})
    new_state = {"step": step + 1, "sections": new_sections}
    metrics = {"grad_norm": gnorm}
    return new_params, new_state, metrics
