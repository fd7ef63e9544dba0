"""N-tier FabricSpec tentpole tests.

Covers the PR's acceptance criteria:
  * recursive ``dfabric_all_reduce`` / ``dfabric_all_to_all`` match flat
    ``lax.psum`` / ``lax.all_to_all`` on 1-, 2- and 3-tier meshes (8 forced
    CPU devices, 2x2x2),
  * ``CostModel.ntier_striped`` charges every tier and is monotone in the
    slowest tier's bandwidth,
  * ``Planner.plan`` on a 3-tier fabric emits per-tier scatter depths that
    ``grad_sync`` consumes end-to-end,
  * ``TwoTierTopology`` compatibility surface is unchanged.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import run_multi_device

HERE = os.path.dirname(os.path.abspath(__file__))


# ---------------------------------------------------------------------------
# pure-topology units (no devices needed)
# ---------------------------------------------------------------------------


def _fabric3(bw_slow=6.25e9):
    from repro.core.topology import three_tier_fabric
    fab = three_tier_fabric(num_pods=2, hosts_per_pod=2, chips_per_host=2)
    return fab.with_slowest_bw(bw_slow)


def test_fabric_spec_structure():
    from repro.core.topology import FabricSpec, Tier, TwoTierTopology
    fab = _fabric3()
    assert fab.depth == 3
    assert fab.axes == ("data", "host", "pod")
    assert fab.fast_axes == ("data", "host")
    assert fab.slow_axis == "pod"
    assert fab.n_fast == 4 and fab.total_chips == 8
    assert fab.members_below(0) == 1
    assert fab.members_below(2) == 4
    # duplicate axes rejected
    with pytest.raises(ValueError):
        FabricSpec(tiers=(Tier("a", "x", 2, 1e9, 1e-6),
                          Tier("b", "x", 2, 1e9, 1e-6)))
    # two-tier view keeps the legacy surface
    two = fab.as_two_tier()
    assert isinstance(two, TwoTierTopology)
    assert two.num_pods == 2 and two.chips_per_pod == 4


def test_two_tier_topology_compat_unchanged():
    """The legacy constructor and its derived quantities still work."""
    from repro.core.topology import TwoTierTopology, as_fabric
    topo = TwoTierTopology(num_pods=2, pod_shape=(16, 16), dcn_lanes=2.0)
    assert topo.chips_per_pod == 256
    assert topo.total_chips == 512
    assert topo.pool_dcn_bw == 256 * topo.hw.dcn_bw * 2.0
    fab = as_fabric(topo)
    assert fab.depth == 2
    assert fab.slowest.lanes == 2.0
    assert fab.n_fast == 256


def test_fabric_from_mesh_sizes_tiers():
    from repro.core.topology import fabric_from_mesh_sizes
    f1 = fabric_from_mesh_sizes({"data": 8})
    f2 = fabric_from_mesh_sizes({"data": 4, "pod": 2})
    f3 = fabric_from_mesh_sizes({"data": 2, "host": 2, "pod": 2})
    assert (f1.depth, f2.depth, f3.depth) == (1, 2, 3)
    assert f3.axes == ("data", "host", "pod")
    # TP chips stripe too: "model" folds into the fastest tier's size
    fm = fabric_from_mesh_sizes({"data": 4, "model": 16, "pod": 2})
    assert fm.tiers[0].size == 64 and fm.depth == 2
    # size-1 axes are skipped (a single-pod mesh has no DCN tier)
    fs = fabric_from_mesh_sizes({"data": 4, "host": 2, "pod": 1})
    assert fs.depth == 2 and fs.axes == ("data", "host")


def test_ntier_cost_degenerate_fabrics():
    """A 1-tier fabric charges its single tier a full ring all-reduce, and
    a size-1 slow tier is charged zero (not a fast tier's bytes)."""
    from repro.core.cost_model import CostModel
    from repro.core.topology import fabric_from_mesh_sizes, three_tier_fabric
    one = CostModel(fabric_from_mesh_sizes({"data": 8}))
    est = one.ntier_striped(64 << 20)
    assert est.total_s > 0 and len(est.charges) == 1
    assert est.charges[0].tier == "ici" and not est.charges[0].scattered
    deg = CostModel(three_tier_fabric(num_pods=1, hosts_per_pod=2,
                                      chips_per_host=2))
    est = deg.ntier_striped(64 << 20)
    assert est.charges[-1].tier == "dcn"
    assert est.slow_bytes_per_chip == 0.0 and est.slow_s == 0.0
    assert est.fast_s > 0


# ---------------------------------------------------------------------------
# cost model
# ---------------------------------------------------------------------------


def test_ntier_cost_charges_every_tier():
    from repro.core.cost_model import CostModel
    cm = CostModel(_fabric3())
    est = cm.ntier_striped(64 << 20, scatter_depth=-1)
    assert len(est.charges) == 3
    assert [c.tier for c in est.charges] == ["ici", "cxl", "dcn"]
    assert all(c.seconds > 0 for c in est.charges)
    # fast tiers scattered, slow leg not
    assert est.charges[0].scattered and est.charges[1].scattered
    assert not est.charges[2].scattered
    # striping: the slow leg carries 1/n_fast of the payload per chip
    shallow = cm.ntier_striped(64 << 20, scatter_depth=0)
    assert est.slow_bytes_per_chip * 4 == pytest.approx(
        shallow.slow_bytes_per_chip)


@pytest.mark.parametrize("nbytes", [1 << 20, 64 << 20, 1 << 30])
def test_ntier_cost_monotone_in_slow_bw(nbytes):
    """A 3-tier plan's estimate must improve as the slowest tier speeds up."""
    from repro.core.cost_model import CostModel
    bws = [1e9, 5e9, 25e9, 100e9]
    times = [CostModel(_fabric3(bw)).ntier_striped(nbytes).total_s
             for bw in bws]
    assert all(a > b for a, b in zip(times, times[1:])), times


def test_ntier_best_prefers_deeper_scatter():
    """In the alpha-beta model, scattering over more fast tiers never makes
    the slow leg slower; the best plan uses full depth for large payloads."""
    from repro.core.cost_model import CostModel
    cm = CostModel(_fabric3())
    best = cm.ntier_best(256 << 20)
    assert best.scatter_depth == 2


# ---------------------------------------------------------------------------
# planner on a 3-tier fabric
# ---------------------------------------------------------------------------


def test_planner_emits_per_tier_depths():
    from repro.core.planner import Planner
    fab = _fabric3()
    planner = Planner(fab, strategy="hier_striped")
    shapes = {
        # divisible by 2*2 -> full depth (-1)
        "deep": jax.ShapeDtypeStruct((8, 1024), jnp.float32),
        # every dim divisible by 2 but not 4 -> depth 1 (fastest tier only)
        "shallow": jax.ShapeDtypeStruct((6, 1022), jnp.float32),
        # indivisible -> flat
        "odd": jax.ShapeDtypeStruct((5, 7), jnp.float32),
    }
    plan = planner.plan(shapes, bucket_bytes=1)
    by_name = {s.name: s for s in plan.sections}
    assert by_name["deep"].sync.scatter_depth == -1
    assert by_name["shallow"].sync.scatter_depth == 1
    assert by_name["odd"].sync.strategy == "flat"
    assert plan.est_total_s > 0


def test_planner_cost_monotone_in_slow_bw():
    from repro.core.planner import Planner
    shapes = {"w": jax.ShapeDtypeStruct((64, 4096), jnp.float32)}
    costs = [Planner(_fabric3(bw), strategy="hier_striped").plan(shapes).est_total_s
             for bw in (1e9, 10e9, 100e9)]
    assert costs[0] > costs[1] > costs[2], costs


def test_planner_two_tier_call_sites_unchanged():
    """A TwoTierTopology planner on the mesh's own fast axis keeps
    working."""
    from repro.core.planner import Planner
    from repro.core.topology import TwoTierTopology
    topo = TwoTierTopology(num_pods=2, pod_shape=(2, 2))
    planner = Planner(topo, fast_axes=(("data", 2),), strategy="hier_striped")
    plan = planner.plan({"w": jax.ShapeDtypeStruct((8, 16), jnp.float32)},
                        bucket_bytes=1)
    assert plan.sections[0].sync.scatter_depth == -1
    assert planner.fast_sizes == (2,)


# ---------------------------------------------------------------------------
# CommSchedule IR (no devices needed)
# ---------------------------------------------------------------------------


def test_schedule_build_decisions():
    """The builder owns the tier walk: scatters the divisible prefix,
    psums the rest, chunks the slow leg, all-gathers back in reverse."""
    from repro.core.schedule import (AllGather, Psum, ReduceScatter,
                                     SlowChunk, SyncConfig, build_schedule)
    fab = _fabric3()
    s = build_schedule(fab, SyncConfig("hier_striped", chunks=4),
                       (8, 1024), 1)
    kinds = [type(l).__name__ for l in s.legs]
    assert kinds == ["ReduceScatter", "ReduceScatter"] \
        + ["SlowChunk"] * 4 + ["AllGather", "AllGather"]
    assert s.pipelined and s.chunks == 4
    # pipelined chunking must keep every chunk divisible by the scattered
    # prefix: dim extent 8 with 4 scattered members clamps 4 -> 2 chunks
    s8 = build_schedule(fab, SyncConfig("hier_striped", chunks=4),
                        (8, 1024), 0)
    assert s8.chunks == 2 and s8.pipelined
    assert s.scattered_axes == ("data", "host")
    assert s.up_legs[0].axis == "host" and s.up_legs[1].axis == "data"
    # depth-limited plan: the mid tier beyond the depth is psum'ed
    s1 = build_schedule(fab, SyncConfig("hier_striped", scatter_depth=1),
                        (6, 1022), 0)
    assert [type(l).__name__ for l in s1.legs] == \
        ["ReduceScatter", "Psum", "SlowChunk", "AllGather"]
    # indivisible by the planned prefix -> flat fallback (and a full-depth
    # request on a dim only the fastest tier divides falls back the same
    # way the retired recursion's precheck did)
    assert build_schedule(fab, SyncConfig("hier_striped"),
                          (6, 1022), 0).strategy == "flat"
    sf = build_schedule(fab, SyncConfig("hier_striped"), (5, 7), 0)
    assert sf.strategy == "flat"
    assert all(isinstance(l, Psum) for l in sf.legs)
    # hier_root: psum the fast tiers, full payload on the slow leg
    sr = build_schedule(fab, SyncConfig("hier_root", chunks=2), (8, 8), 0)
    assert [type(l).__name__ for l in sr.legs] == \
        ["Psum", "Psum", "SlowChunk", "SlowChunk"]
    # top-k never chunks; pipeline needs chunks>1 AND a scattered tier
    st = build_schedule(fab, SyncConfig("hier_striped", chunks=4,
                                        codec="topk"), (8, 1024), 0)
    assert st.chunks == 1 and not st.pipelined


def test_schedule_json_roundtrip():
    from repro.core.schedule import CommSchedule, SyncConfig, build_schedule
    fab = _fabric3()
    for cfg in (SyncConfig("hier_striped", chunks=4, codec="int8"),
                SyncConfig("hier_striped", scatter_depth=1,
                           mid_codec="int8"),
                SyncConfig("hier_root"),
                SyncConfig("flat")):
        s = build_schedule(fab, cfg, (8, 1024), 0)
        rt = CommSchedule.from_json(s.to_json())
        assert rt == s, cfg
        assert rt.describe() == s.describe()


def test_from_schedule_matches_ntier_striped():
    """On a fully-divisible shape the schedule price equals the legacy
    shape-free formula — the drift between the cost model and the executed
    recursion is retired."""
    from repro.core.cost_model import CostModel
    from repro.core.schedule import SyncConfig, build_schedule
    fab = _fabric3()
    cm = CostModel(fab)
    numel = (64 << 20) // 4
    for chunks in (1, 4):
        s = build_schedule(fab, SyncConfig("hier_striped", chunks=chunks,
                                           pipeline=False), (numel,), 0)
        est = cm.from_schedule(s)
        ref = cm.ntier_striped(64 << 20, scatter_depth=-1, chunks=chunks)
        assert est.total_s == pytest.approx(ref.total_s, rel=1e-12), chunks
        assert est.slow_bytes_per_chip == pytest.approx(
            ref.slow_bytes_per_chip)


def test_from_schedule_prices_the_lowered_legs():
    """Acceptance: the cost model walks the SAME CommSchedule the executor
    lowers — leg_charges[i].leg IS schedule.legs[i]."""
    from repro.core.cost_model import CostModel
    from repro.core.schedule import SyncConfig, build_schedule
    fab = _fabric3()
    s = build_schedule(fab, SyncConfig("hier_striped", chunks=4), (8, 1024), 1)
    est = CostModel(fab).from_schedule(s)
    assert len(est.leg_charges) == len(s.legs)
    assert all(lc.leg is l for lc, l in zip(est.leg_charges, s.legs))
    assert est.pipelined and est.chunks == 4


def test_from_schedule_overlap_credit():
    """Pipelined schedules are credited max(slow, fast) + min(per-chunk),
    strictly cheaper than the sequential sum of the same legs."""
    from repro.core.cost_model import CostModel
    from repro.core.schedule import SyncConfig, build_schedule
    fab = _fabric3()
    cm = CostModel(fab)
    numel = (64 << 20) // 4
    seq = cm.from_schedule(build_schedule(
        fab, SyncConfig("hier_striped", chunks=4, pipeline=False), (numel,), 0))
    ovl = cm.from_schedule(build_schedule(
        fab, SyncConfig("hier_striped", chunks=4, pipeline=True), (numel,), 0))
    assert ovl.total_s < seq.total_s
    slow = sum(lc.seconds for lc in ovl.leg_charges
               if type(lc.leg).__name__ == "SlowChunk")
    fast = sum(lc.seconds for lc in ovl.leg_charges
               if type(lc.leg).__name__ != "SlowChunk")
    assert ovl.total_s == pytest.approx(
        max(slow, fast) + min(slow / 4, fast / 4))
    assert seq.total_s == pytest.approx(slow + fast)


def test_planner_stores_schedule_on_sections():
    from repro.core.planner import Planner
    fab = _fabric3()
    plan = Planner(fab, strategy="hier_striped").plan(
        {"w": jax.ShapeDtypeStruct((8, 1024), jnp.float32)}, bucket_bytes=1)
    sec = plan.sections[0]
    assert sec.schedule is not None
    assert sec.schedule.scattered_axes == ("data", "host")
    assert sec.schedule.chunks == sec.sync.chunks
    # the serialized plan embeds the schedule
    import json as _json
    dumped = _json.loads(plan.to_json())
    assert dumped[0]["schedule"]["legs"][0]["kind"] == "reduce_scatter"


def test_planner_bucket_chunks_not_hardcoded():
    """Regression: flush() used to hard-code chunks=1 for small-leaf
    buckets; now the searched chunk count (clamped by the schedule
    builder) lands in the emitted Section."""
    from repro.core.planner import Planner
    fab = _fabric3()
    planner = Planner(fab, strategy="hier_striped", max_chunks=4)
    shapes = {f"b{i}": jax.ShapeDtypeStruct((1 << 16,), jnp.float32)
              for i in range(16)}
    plan = planner.plan(shapes, bucket_bytes=32 << 20)
    bucket = [s for s in plan.sections if len(s.leaf_paths) > 1]
    assert bucket, "expected a bucket section"
    sec = bucket[0]
    assert sec.sync.chunks > 1
    assert sec.schedule is not None and sec.schedule.chunks == sec.sync.chunks
    padded = sec.numel + ((-sec.numel) % planner.nf)
    assert (padded // planner.nf) % sec.sync.chunks == 0


def test_planner_chunks_use_real_itemsize():
    """Regression: chunk feasibility used nbytes // 4 (assumed fp32).
    Feasibility is now driven by the true element count; schedule pricing
    honors the schedule's dtype (the planner prices at the fp32 WIRE
    dtype, since grad_sync upcasts before the collectives)."""
    from repro.core.cost_model import CostModel, dtype_itemsize
    from repro.core.planner import Planner
    from repro.core.schedule import SyncConfig, build_schedule
    assert dtype_itemsize("float16") == 2
    assert dtype_itemsize("bfloat16") == 2
    fab = _fabric3()
    # min_chunk_numel exactly at the 2-way split of the true shard numel:
    # an fp32-assuming byte count (nbytes // 4 == true_numel / 2 for fp16)
    # would have rejected every chunking of this fp16 section
    shard_numel = (1024 * 4096) // 4
    planner = Planner(fab, strategy="hier_striped",
                      min_chunk_numel=shard_numel // 2, max_chunks=2)
    plan = planner.plan({"w16": jax.ShapeDtypeStruct((1024, 4096),
                                                     jnp.float16)},
                        bucket_bytes=1)
    sec = plan.sections[0]
    assert sec.sync.chunks == 2
    # and the cost model charges half-precision half the bytes
    cm = CostModel(fab)
    cfg = SyncConfig("hier_striped", pipeline=False)
    e16 = cm.from_schedule(build_schedule(fab, cfg, (64, 4096), 1,
                                          dtype="float16"))
    e32 = cm.from_schedule(build_schedule(fab, cfg, (64, 4096), 1,
                                          dtype="float32"))
    assert e16.slow_bytes_per_chip == pytest.approx(
        e32.slow_bytes_per_chip / 2)


def test_planner_mid_tier_codec_legal():
    """The second ROADMAP open item: a depth-limited section may compress
    its UNSCATTERED mid tier."""
    from repro.core.planner import Planner
    from repro.core.schedule import Psum
    fab = _fabric3()
    planner = Planner(fab, strategy="hier_striped", mid_codec="int8")
    # dims divisible by 2 but not 4 -> depth 1, cxl tier psum'ed
    plan = planner.plan({"w": jax.ShapeDtypeStruct((2, 524286), jnp.float32)},
                        bucket_bytes=1)
    sec = plan.sections[0]
    assert sec.sync.scatter_depth == 1
    assert sec.sync.mid_codec == "int8"
    mid = [l for l in sec.schedule.legs if isinstance(l, Psum)]
    assert mid and mid[0].codec == "int8"


# ---------------------------------------------------------------------------
# multi-device equivalence batteries (8 forced CPU devices, subprocess)
# ---------------------------------------------------------------------------


def test_multi_device_ntier_battery():
    out = run_multi_device(os.path.join(HERE, "batteries", "ntier_battery.py"))
    assert "ALL OK" in out


@pytest.fixture(scope="module")
def planned_axes():
    out = run_multi_device(os.path.join(HERE, "batteries",
                                        "sync_plan_axes.py"))
    return json.loads(out.strip().splitlines()[-1])


@pytest.mark.parametrize("shape", [(1, 2, 1), (1, 4, 1), (1, 2, 2),
                                   (2, 2, 1)], ids=str)
def test_sync_plan_names_only_mesh_axes(planned_axes, shape):
    """``make_sync_plan`` plans every Section on the mesh's own axes, so
    the executor lowers the schedule the planner priced (one-host data
    parallelism included: ``pod`` = 1, ``data`` > 1)."""
    got = planned_axes[str(list(shape))]
    assert got["sections"]
    for name, axes in got["sections"].items():
        assert set(axes) <= set(got["mesh_axes"]), (name, axes)
    assert any("data" in axes for axes in got["sections"].values())


def test_multi_device_schedule_battery():
    out = run_multi_device(os.path.join(HERE, "batteries",
                                        "schedule_battery.py"))
    assert "ALL OK" in out
