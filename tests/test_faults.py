"""Fault injection through the event loop: mid-run lane/expander/tenant
deaths, the degraded fabric's static twin (``FabricSpec.degrade``), the
planner's elastic replan + ``PlanDiff``, and the ``degraded`` audit
contract class."""
import jax
import numpy as np
import pytest

from repro.core.cost_model import CostModel
from repro.core.mempool import MemPoolSpec
from repro.core.nicpool import NicPool
from repro.core.planner import Planner
from repro.core.schedule import SyncConfig, build_schedule
from repro.core.topology import (as_fabric, cxl_shortcut_path,
                                 paper_prototype_topology,
                                 three_tier_fabric)
from repro.sim.fabric_sim import (Tenant, device_down, lane_down, simulate,
                                  tenant_down)


def _fab():
    return three_tier_fabric(num_pods=2, hosts_per_pod=2, chips_per_host=2)


def _sched(fab, numel=1 << 18, chunks=2):
    return build_schedule(fab, SyncConfig("hier_striped", chunks=chunks,
                                          pipeline=False), (numel,), 0)


# ---------------------------------------------------------------------------
# event-loop failure consumption
# ---------------------------------------------------------------------------


def test_lane_down_binds_and_records_capacity_step():
    """Two CN streams on a shared rack pool: losing most of the pool
    mid-run stretches the makespan, and the arbiter's capacity trace
    records when."""
    fab = _fab()
    s = _sched(fab)
    tenants = lambda: [Tenant("cn0", s, rounds=2), Tenant("cn1", s, rounds=2)]
    healthy = simulate(fab, tenants(), pool=NicPool(lanes=fab.pool_lanes))
    t_fail = healthy.makespan / 4
    lost = fab.pool_lanes - 0.5
    deg = simulate(fab, tenants(), pool=NicPool(lanes=fab.pool_lanes),
                   failures=[lane_down(t_fail, lanes=lost)])
    assert deg.makespan > healthy.makespan * 1.05
    assert deg.failed_tenants == ()
    assert deg.pool.capacity_steps == [(0.0, fab.pool_lanes),
                                       (t_fail, fab.pool_lanes - lost)]
    assert deg.pool.degraded_since() == t_fail


def test_tenant_down_truncates_and_unblocks_successor():
    """A departed CN's events truncate at the kill time and its ``after``
    successor starts immediately instead of waiting out the full run."""
    fab = _fab()
    s = _sched(fab)
    mk = lambda: [Tenant("a", s, rounds=4),
                  Tenant("b", s, rounds=1, after="a")]
    ref = simulate(fab, mk(), pool=NicPool(lanes=fab.pool_lanes))
    t_kill = ref.finish["a"] * 0.25
    res = simulate(fab, mk(), pool=NicPool(lanes=fab.pool_lanes),
                   failures=[tenant_down(t_kill, "a")])
    assert res.failed_tenants == ("a",)
    assert res.finish["a"] == pytest.approx(t_kill)
    assert all(e.finish <= t_kill + 1e-12 for e in res.tenant_events("a"))
    assert res.finish["b"] < ref.finish["b"]
    # the survivor still ran its whole program
    assert res.tenant_events("b")


def test_device_down_restripes_mid_run():
    """An expander death under a pool-staged stream turns it memory-bound
    for the rest of the run (deliverable drops below the wire)."""
    mem = MemPoolSpec.build(local_bw=100e9, local_channels=2,
                            device_bw=1.5e9, devices=4,
                            device_latency=2e-6)
    fab = as_fabric(paper_prototype_topology()).with_mem(mem)
    cfg = SyncConfig("hier_striped", chunks=4, pipeline=False)
    sched = build_schedule(fab, cfg, (1 << 20,)).with_staging("pool")
    cm = CostModel(fab)
    healthy = simulate(fab, [Tenant("t0", sched, rounds=2)], cost=cm)
    deg = simulate(fab, [Tenant("t0", sched, rounds=2)], cost=cm,
                   failures=[device_down(healthy.makespan / 2, "cxl3")])
    assert deg.makespan > healthy.makespan * 1.01
    assert deg.mem is not None and deg.mem.degraded_since() is not None
    assert [d.name for d in deg.mem.spec.devices].count("cxl3") == 0


def test_failure_validation():
    fab = _fab()
    s = _sched(fab)
    mk = lambda: [Tenant("t", s)]
    with pytest.raises(ValueError, match="unknown lane group"):
        simulate(fab, mk(), failures=[lane_down(0.0, path="nvlink")])
    with pytest.raises(ValueError, match="no co-simulated memory pool"):
        simulate(fab, mk(), failures=[device_down(0.0, "cxl0")])
    with pytest.raises(ValueError, match="unknown tenant"):
        simulate(fab, mk(), failures=[tenant_down(0.0, "ghost")])
    from repro.sim.fabric_sim import FailureEvent
    with pytest.raises(ValueError, match="unknown failure kind"):
        simulate(fab, mk(), failures=[FailureEvent(0.0, "asteroid")])


# ---------------------------------------------------------------------------
# FabricSpec.degrade — the post-failure static twin
# ---------------------------------------------------------------------------


def test_degrade_pool_lanes():
    fab = _fab()
    deg = fab.degrade(pool_lanes=3.0)
    assert deg.pool_lanes == pytest.approx(fab.pool_lanes - 3.0)
    assert deg.depth == fab.depth
    with pytest.raises(ValueError):
        fab.degrade(pool_lanes=fab.pool_lanes)  # nothing would survive


def test_degrade_tier_members_and_mem():
    mem = MemPoolSpec.build(local_bw=100e9, device_bw=10e9, devices=2)
    fab = _fab().with_mem(mem)
    deg = fab.degrade(tier_members={"dcn": 1}, mem_devices=["cxl1"])
    assert deg.slowest.size == fab.slowest.size - 1
    assert [d.name for d in deg.mem.devices] == ["dram0", "dram1", "cxl0"]
    with pytest.raises(KeyError):
        fab.degrade(tier_members={"warp": 1})
    with pytest.raises(ValueError):
        fab.degrade(tier_members={"dcn": fab.slowest.size})
    with pytest.raises(KeyError):
        fab.degrade(mem_devices=["cxl9"])
    with pytest.raises(ValueError):
        _fab().degrade(mem_devices=["cxl0"])  # no memory model attached


# ---------------------------------------------------------------------------
# elastic replan + PlanDiff
# ---------------------------------------------------------------------------


def test_replan_diff_names_the_knob_flips():
    fab = _fab().with_paths(cxl_shortcut_path(lanes=2.0))
    shapes = {"w": jax.ShapeDtypeStruct((1 << 20,), np.float32)}
    planner = Planner(fab, max_chunks=4)
    plan = planner.plan(shapes)
    new_plan, diff = planner.replan(fab.degrade(pool_lanes=3.5), shapes,
                                    old_plan=plan, reason="lane_down")
    assert diff.changed and diff.reason == "lane_down"
    # the eth pool collapsed, so the winner reroutes onto the cxl path
    assert any(d.knob == "path_split" for d in diff.deltas)
    assert "lane_down" in diff.describe()
    assert all(d.section and "->" in d.describe() for d in diff.deltas)
    assert new_plan.est_total_s > 0

    # no old plan: everything reports as added, nothing as changed knobs
    _, fresh = planner.replan(fab.degrade(pool_lanes=3.5), shapes)
    assert fresh.changed and set(fresh.added) == {s.name for s in
                                                  new_plan.sections}
    assert fresh.deltas == () and fresh.removed == ()


def test_for_fabric_rederives_fast_sizes():
    fab = _fab()
    planner = Planner(fab, max_chunks=4)
    deg = fab.degrade(tier_members={"ici": 1})
    assert planner.for_fabric(deg).fast_sizes != planner.fast_sizes
    # explicit override survives the move to the degraded fabric
    pinned = Planner(fab, fast_axes=(("data", 2), ("host", 2)), max_chunks=4)
    assert pinned.for_fabric(deg).fast_sizes == (2, 2)


# ---------------------------------------------------------------------------
# the `degraded` audit contract class
# ---------------------------------------------------------------------------


def test_degraded_runs_audit_in_class():
    from repro.obs.audit import audit_observation
    from repro.obs.capture import capture
    fab = _fab()
    s = _sched(fab)
    with capture() as observations:
        healthy = simulate(fab, [Tenant("cn0", s, rounds=2),
                                 Tenant("cn1", s, rounds=2)],
                           pool=NicPool(lanes=fab.pool_lanes))
        simulate(fab, [Tenant("cn0", s, rounds=2),
                       Tenant("cn1", s, rounds=2)],
                 pool=NicPool(lanes=fab.pool_lanes),
                 failures=[lane_down(healthy.makespan / 4,
                                     lanes=fab.pool_lanes - 0.5)])
    assert len(observations) == 2
    deg_rep = audit_observation(observations[1])
    assert deg_rep.ok, deg_rep.describe()
    assert any(r.cls == "degraded" for r in deg_rep.rows), \
        deg_rep.describe()
