"""Hypothesis property tests on system invariants: compression codecs,
cost model, planner, bucketing."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="hypothesis not installed (see requirements-dev.txt)")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core.compression import Int8Codec, TopKCodec
from repro.core.cost_model import CostModel
from repro.core.planner import Planner
from repro.core.schedule import (AllToAll, CommSchedule, SlowChunk,
                                 SyncConfig, all_to_all_from_axes)
from repro.core.topology import TwoTierTopology, as_fabric

TOPO = TwoTierTopology()
CM = CostModel(TOPO)

# a 4-rack x 2-CN fabric for the skewed (dest_sizes) properties: joint
# DP domain of 8 members (data=2 fast, pod=4 slow), tiers named like the
# prototype
SKEW_FAB = as_fabric(TwoTierTopology(num_pods=4, pod_shape=(2,)))
SKEW_CM = CostModel(SKEW_FAB)
SKEW_NAMES = {"data": "ici", "pod": "dcn"}
SKEW_SIZES = {"data": 2, "pod": 4}
SKEW_SHAPE = (8, 1 << 10)


def _skew_sched(weights, chunks=1):
    """Skewed 8-member all-to-all whose per-member wire bytes follow
    ``weights`` (None -> the uniform schedule of the same payload)."""
    ds = None
    if weights is not None:
        total = SKEW_SHAPE[0] * SKEW_SHAPE[1] * 4.0
        ds = [total * w / sum(weights) for w in weights]
    return all_to_all_from_axes(("data",), "pod",
                                SyncConfig(chunks=chunks), SKEW_SHAPE,
                                SKEW_SIZES, tier_names=SKEW_NAMES,
                                dest_sizes=ds)


# ---------------------------------------------------------------------------
# codecs
# ---------------------------------------------------------------------------


@settings(max_examples=30, deadline=None)
@given(st.integers(1, 8), st.floats(0.01, 100.0), st.integers(0, 2**31 - 1))
def test_int8_roundtrip_bounded(nblocks, scale, seed):
    """|x - decode(encode(x))| <= scale/127 per block (quantization bound)."""
    block = 64
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal(nblocks * block).astype(np.float32) * scale)
    codec = Int8Codec(block=block)
    q, s = codec.encode(x)
    err = np.abs(np.asarray(x - codec.decode(q, s)))
    bound = np.repeat(np.asarray(s), block) * 0.5 + 1e-9
    assert (err <= bound + 1e-6).all()


@settings(max_examples=30, deadline=None)
@given(st.integers(1, 6), st.integers(0, 2**31 - 1))
def test_int8_error_feedback_invariant(nblocks, seed):
    """x + ef == decode(q) + new_ef exactly (EF captures all error)."""
    block = 64
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal(nblocks * block).astype(np.float32))
    ef = jnp.asarray(rng.standard_normal(nblocks * block).astype(np.float32) * 0.1)
    codec = Int8Codec(block=block)
    q, s = codec.encode(x + ef)
    new_ef = (x + ef) - codec.decode(q, s)
    np.testing.assert_allclose(np.asarray(x + ef),
                               np.asarray(codec.decode(q, s) + new_ef),
                               rtol=1e-6, atol=1e-6)


@settings(max_examples=30, deadline=None)
@given(st.integers(16, 512), st.floats(0.05, 1.0), st.integers(0, 2**31 - 1))
def test_topk_keeps_largest(n, frac, seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal(n).astype(np.float32))
    codec = TopKCodec(k_frac=frac)
    vals, idx = codec.encode(x)
    dec = codec.decode(vals, idx, n)
    k = codec.k_of(n)
    # the reconstruction keeps exactly the k largest-magnitude entries
    kept = np.sort(np.abs(np.asarray(vals)))
    thresh = np.sort(np.abs(np.asarray(x)))[-k]
    assert kept[0] >= thresh - 1e-6
    # everything kept matches x at those indices
    xi = np.asarray(x)[np.asarray(idx)]
    np.testing.assert_allclose(np.asarray(vals), xi, rtol=1e-6)
    # wire bytes strictly less than dense for frac < 0.5
    if frac < 0.5:
        assert codec.wire_bytes(n) < n * 4


# ---------------------------------------------------------------------------
# cost model
# ---------------------------------------------------------------------------


@settings(max_examples=40, deadline=None)
@given(st.floats(1e4, 1e10))
def test_cost_model_ordering(nbytes):
    """Striped NIC pool <= single-root <= flat ring crossing DCN, always."""
    flat = CM.flat_ring(nbytes).total_s
    root = CM.hierarchical(nbytes, striped=False).total_s
    striped = CM.hierarchical(nbytes, striped=True).total_s
    assert striped <= root * (1 + 1e-9)
    assert root <= flat * (1 + 1e-9)


@settings(max_examples=30, deadline=None)
@given(st.floats(1e5, 1e9), st.floats(1.5, 16.0))
def test_compression_helps_dcn(nbytes, ratio):
    base = CM.hierarchical(nbytes, striped=True)
    comp = CM.hierarchical(nbytes, striped=True, compression_ratio=ratio)
    assert comp.dcn_s <= base.dcn_s * (1 + 1e-9)
    assert comp.total_s <= base.total_s * (1 + 1e-9)


@settings(max_examples=20, deadline=None)
@given(st.floats(1e5, 1e9), st.integers(1, 8))
def test_more_nics_never_slower(nbytes, lanes):
    t1 = CostModel(TOPO.replace(dcn_lanes=1.0)).hierarchical(nbytes).total_s
    t2 = CostModel(TOPO.replace(dcn_lanes=float(lanes))).hierarchical(nbytes).total_s
    assert t2 <= t1 * (1 + 1e-9)


# ---------------------------------------------------------------------------
# skewed (per-destination) all-to-all
# ---------------------------------------------------------------------------

skew_weights = st.lists(st.floats(0.0, 10.0), min_size=8, max_size=8) \
    .filter(lambda w: max(w) > 1e-3)


@settings(max_examples=40, deadline=None)
@given(skew_weights, st.integers(1, 4))
def test_skewed_pricing_never_beats_uniform(weights, chunks):
    """The incast bound charges the hottest destination row, so a skewed
    exchange moving the SAME total bytes can never price below the
    uniform (rectangular) schedule — per leg, (n-1)*max(dest_sizes) >=
    (n-1)*mean(dest_sizes) == the uniform wire bytes."""
    uni = SKEW_CM.from_schedule(_skew_sched(None, chunks)).total_s
    skw = SKEW_CM.from_schedule(_skew_sched(weights, chunks)).total_s
    assert skw >= uni * (1 - 1e-9)


@settings(max_examples=40, deadline=None)
@given(skew_weights, st.integers(1, 4))
def test_builder_digit_sums_conserve_bytes(weights, chunks):
    """The builder's per-tier digit aggregation is a partition of the
    joint-domain profile: every leg's dest_sizes sum to the total wire
    bytes (SlowChunk sub-flows each carry an equal 1/chunks slice), and
    each leg carries one size per member of ITS tier."""
    s = _skew_sched(weights, chunks)
    total = SKEW_SHAPE[0] * SKEW_SHAPE[1] * 4.0
    for leg in s.legs:
        if isinstance(leg, AllToAll):
            assert len(leg.dest_sizes) == leg.size
            assert sum(leg.dest_sizes) == pytest.approx(total)
    slow = s.slow_legs
    if slow:
        assert all(len(l.dest_sizes) == l.size for l in slow)
        assert sum(sum(l.dest_sizes) for l in slow) == pytest.approx(total)


@settings(max_examples=40, deadline=None)
@given(skew_weights, st.integers(1, 4))
def test_skewed_schedule_json_round_trips(weights, chunks):
    """dest_sizes survive to_json/from_json exactly, and the uniform
    schedule's wire format stays byte-identical to the pre-skew one
    (no dest_sizes key when None)."""
    skw = _skew_sched(weights, chunks)
    assert CommSchedule.from_json(skw.to_json()) == skw
    uni = _skew_sched(None, chunks)
    assert "dest_sizes" not in uni.to_json()
    assert CommSchedule.from_json(uni.to_json()) == uni


# ---------------------------------------------------------------------------
# hierarchical all-to-all (dfabric_all_to_all's stage walk)
# ---------------------------------------------------------------------------
#
# A numpy model of lax.all_to_all's global semantics drives the SAME leg
# list ``collectives.lower_all_to_all`` walks (built by
# ``all_to_all_from_axes``), so the slow-major row-ordering and stage-dim
# arithmetic are checked over RANDOM tier sizes — depths/extents no
# 8-device battery mesh can reach.  State: G[(mesh coords slowest-first)
# + (row,) + rest] = each member's local payload; one tier's exchange is
# the block transpose of that tier's member axis with its own row
# sub-index in the slow-major view.


def _np_stage(G, mesh_shape, pos):
    """all_to_all over mesh axis ``pos`` (slowest-first index), split ==
    concat == that axis's own sub-index of the row dim."""
    k = len(mesh_shape)
    rest = G.shape[k + 1:]
    H = G.reshape(*mesh_shape, *mesh_shape, *rest)  # rows slow-major
    return np.swapaxes(H, pos, k + pos).reshape(G.shape)


def _np_flat(G, mesh_shape):
    """One all_to_all over the JOINT (slowest, ..., fastest) domain."""
    k = len(mesh_shape)
    rest = G.shape[k + 1:]
    H = G.reshape(*mesh_shape, *mesh_shape, *rest)
    perm = list(range(k, 2 * k)) + list(range(k)) \
        + list(range(2 * k, H.ndim))
    return H.transpose(perm).reshape(G.shape)


def _np_lower(G, mesh_shape, sched):
    """Walk the schedule's legs in the numpy model (fastest tier first;
    every SlowChunk sub-flow exchanges the slow axis once — the chunked
    lowering is the same permutation per payload slice)."""
    if not sched.legs:  # fully degenerate domain: identity
        return G
    k = len(mesh_shape)
    fast = [l for l in sched.legs if isinstance(l, AllToAll)]
    n_stages = len(fast) + (1 if sched.slow_legs else 0)
    assert n_stages == k, (sched.legs, mesh_shape)
    for i in range(len(fast)):
        G = _np_stage(G, mesh_shape, k - 1 - i)
    if sched.slow_legs:
        G = _np_stage(G, mesh_shape, 0)
    return G


def _a2a_case(draw_sizes, seed, rest=2):
    """(mesh_shape slowest-first, schedule, payload G) for random sizes."""
    fast_sizes = [n for n in draw_sizes[:-1] if n > 1]  # fastest first
    slow = draw_sizes[-1]
    sizes = {f"f{i}": n for i, n in enumerate(fast_sizes)}
    sizes["s"] = slow
    sched = all_to_all_from_axes(
        tuple(f"f{i}" for i in range(len(fast_sizes))),
        "s" if slow > 1 else None,
        SyncConfig(chunks=1), (int(np.prod([n for n in draw_sizes if n > 1],
                                           dtype=np.int64)), rest),
        sizes)
    mesh_shape = tuple(([slow] if slow > 1 else [])
                       + [n for n in reversed(fast_sizes)])
    if not mesh_shape:
        mesh_shape = (1,)
    n_total = int(np.prod(mesh_shape))
    rng = np.random.default_rng(seed)
    G = rng.integers(0, 1 << 20, size=mesh_shape + (n_total, rest))
    return mesh_shape, sched, G


@settings(max_examples=60, deadline=None)
@given(st.lists(st.integers(1, 4), min_size=1, max_size=4),
       st.integers(0, 2**31 - 1))
def test_all_to_all_slow_major_matches_flat(sizes, seed):
    """The hierarchical stage walk == one flat all_to_all over the joint
    domain — the slow-major row-ordering invariant, at random tier sizes
    and depths (bitwise: pure index permutation)."""
    mesh_shape, sched, G = _a2a_case(sizes, seed)
    np.testing.assert_array_equal(_np_lower(G, mesh_shape, sched),
                                  _np_flat(G, mesh_shape))


@settings(max_examples=60, deadline=None)
@given(st.lists(st.integers(1, 4), min_size=1, max_size=4),
       st.integers(0, 2**31 - 1))
def test_all_to_all_inverse_of_itself(sizes, seed):
    """With split == concat (dim 0 both ways), an all-to-all is its own
    inverse — swapping split/concat is the identity transformation, so
    applying the schedule twice returns every payload home."""
    mesh_shape, sched, G = _a2a_case(sizes, seed)
    once = _np_lower(G, mesh_shape, sched)
    np.testing.assert_array_equal(_np_lower(once, mesh_shape, sched), G)
    # the flat reference agrees with itself too
    np.testing.assert_array_equal(_np_flat(_np_flat(G, mesh_shape),
                                           mesh_shape), G)


@settings(max_examples=40, deadline=None)
@given(st.lists(st.integers(2, 4), min_size=2, max_size=3),
       st.integers(0, 2**31 - 1))
def test_all_to_all_legs_cover_domain_once(sizes, seed):
    """Builder invariants at random sizes: one AllToAll leg per active
    fast tier (fastest first), slow sub-flow indices a permutation of
    range(chunks), and the leg sizes multiply to the row count."""
    mesh_shape, sched, _ = _a2a_case(sizes, seed)
    fast = [l for l in sched.legs if isinstance(l, AllToAll)]
    n = int(np.prod([l.size for l in fast], dtype=np.int64))
    slow = sched.slow_legs
    if slow:
        n *= slow[0].size
        assert sorted(l.index for l in slow) == list(range(len(slow)))
    assert n == sched.shape[0] or (n == 1 and sched.shape[0] >= 1)


# ---------------------------------------------------------------------------
# planner
# ---------------------------------------------------------------------------


@settings(max_examples=20, deadline=None)
@given(st.lists(st.tuples(st.integers(1, 2048), st.integers(1, 64)),
                min_size=1, max_size=12),
       st.integers(0, 2**31 - 1))
def test_planner_covers_all_leaves_once(dims, seed):
    shapes = {f"p{i}": jax.ShapeDtypeStruct((a, b), jnp.float32)
              for i, (a, b) in enumerate(dims)}
    plan = Planner(TOPO, fast_axes=(("data", 16),)).plan(shapes, bucket_bytes=1 << 14)
    covered = [p for sec in plan.sections for p in sec.leaf_paths]
    assert sorted(covered) == sorted(shapes)
    for sec in plan.sections:
        if sec.scatter_dim >= 0 and len(sec.leaf_paths) == 1:
            shp = shapes[sec.leaf_paths[0]].shape
            assert shp[sec.scatter_dim] % 16 == 0
            # chunking must divide the ICI shard
            numel = int(np.prod(shp)) // 16
            assert numel % sec.sync.chunks == 0


def test_planner_avoid_dims():
    shapes = {"w": jax.ShapeDtypeStruct((64, 160), jnp.float32)}
    pl = Planner(TOPO, fast_axes=(("data", 16),))
    plan = pl.plan(shapes, bucket_bytes=1 << 10,
                   avoid_dims={"w": frozenset({1})})
    sec = plan.sections[0]
    assert sec.scatter_dim == 0  # 160 avoided though divisible
