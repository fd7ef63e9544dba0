"""All-to-all through the arbiters — unit tests (1 device).

The full lowering/parity battery (``tests/batteries/alltoall_battery.py``)
runs via subprocess with 8 fake devices; these units cover the builder's
clamping/validation, the pricing formulas, the planner search, the
per-destination simulator replay, and the MoE dispatch-schedule threading.
"""
import json
import os

import numpy as np
import pytest

from repro.core.cost_model import CostModel, dtype_itemsize
from repro.core.mempool import MemPoolSpec
from repro.core.nicpool import NicPool
from repro.core.planner import Planner
from repro.core.schedule import (AllToAll, CommSchedule, SlowChunk,
                                 SyncConfig, all_to_all_from_axes,
                                 build_all_to_all)
from repro.core.topology import (TwoTierTopology, as_fabric,
                                 three_tier_fabric)
from repro.sim.fabric_sim import Tenant, simulate
from tests.conftest import run_multi_device

HERE = os.path.dirname(os.path.abspath(__file__))
SIZES3 = {"data": 2, "host": 2, "pod": 2}
NAMES = {"data": "ici", "host": "cxl", "pod": "dcn"}
FAB3 = three_tier_fabric(num_pods=2, hosts_per_pod=2, chips_per_host=2)


# ---------------------------------------------------------------------------
# builder
# ---------------------------------------------------------------------------


def test_builder_legs_and_kind():
    s = all_to_all_from_axes(("data", "host"), "pod", SyncConfig(chunks=2),
                             (8, 16), SIZES3, tier_names=NAMES)
    assert s.kind == "all_to_all"
    assert [type(l).__name__ for l in s.legs] == \
        ["AllToAll", "AllToAll", "SlowChunk", "SlowChunk"]
    assert s.legs[0].tier == "ici" and s.legs[-1].tier == "dcn"
    assert not s.pipelined and s.chunks == 2


def test_builder_clamps_chunks_to_per_slow_row_payload():
    # numel = 8 * 3 = 24, slow rows = 2 -> per-row payload 12; chunks=8
    # walks down to the largest divisor <= 8, i.e. 6
    s = all_to_all_from_axes(("data", "host"), "pod", SyncConfig(chunks=8),
                             (8, 3), SIZES3, tier_names=NAMES)
    assert len(s.slow_legs) == 6 and s.chunks == 6


def test_builder_skips_degenerate_tiers():
    sizes = {"data": 4, "host": 1, "pod": 2}
    s = all_to_all_from_axes(("data", "host"), "pod", SyncConfig(),
                             (8, 4), sizes, tier_names=NAMES)
    assert [l.axis for l in s.legs] == ["data", "pod"]


def test_builder_rejects_codec_and_bad_rows():
    with pytest.raises(ValueError, match="codec"):
        all_to_all_from_axes(("data",), "pod", SyncConfig(codec="int8"),
                             (8, 4), SIZES3)
    with pytest.raises(ValueError, match="row per DP member"):
        all_to_all_from_axes(("data", "host"), "pod", SyncConfig(),
                             (4, 4), SIZES3)
    with pytest.raises(ValueError, match="kind"):
        CommSchedule((), (8,), kind="shuffle")


def test_pipelined_all_to_all_rejected_everywhere():
    """No executor implements an overlapped all-to-all, so a pipelined
    flag must fail at construction AND at plan-JSON load — not be priced
    with a fictional overlap credit."""
    import dataclasses
    s = build_all_to_all(FAB3, SyncConfig(chunks=2), (8, 64))
    assert not s.pipelined  # cfg.pipeline defaults True but cannot apply
    with pytest.raises(ValueError, match="pipelined"):
        dataclasses.replace(s, pipelined=True)
    d = json.loads(s.to_json())
    d["pipelined"] = True  # a hand-edited / corrupted plan
    with pytest.raises(ValueError, match="pipelined"):
        CommSchedule.from_dict(d)


def test_json_round_trip_and_lane_offset():
    s = build_all_to_all(FAB3, SyncConfig(chunks=4), (8, 64)) \
        .with_lane_offset(3).with_staging("local")
    rt = CommSchedule.from_json(s.to_json())
    assert rt == s and rt.kind == "all_to_all"
    assert [l.index for l in rt.slow_legs] == [3, 0, 1, 2]
    # pre-PR-5 JSON (no "collective" key) loads as all_reduce
    d = json.loads(s.to_json())
    del d["collective"]
    assert CommSchedule.from_dict(d).kind == "all_reduce"


# ---------------------------------------------------------------------------
# pricing
# ---------------------------------------------------------------------------


def test_from_schedule_prices_exchange_volumes():
    s = build_all_to_all(FAB3, SyncConfig(chunks=2), (8, 1024))
    est = CostModel(FAB3).from_schedule(s)
    payload = float(s.numel * dtype_itemsize(s.dtype))
    # every tier moves (n-1)/n of the full payload ONCE; payload never
    # shrinks between legs
    for lc, tier in zip(est.leg_charges[:2], FAB3.fast_tiers):
        n = tier.size
        assert lc.bytes_per_chip == pytest.approx((n - 1) / n * payload)
        assert lc.seconds == pytest.approx(
            (n - 1) / n * payload / tier.rate + (n - 1) * tier.latency)
    slow = FAB3.slowest
    for i, lc in enumerate(est.leg_charges[2:]):
        assert lc.bytes_per_chip == pytest.approx(
            (slow.size - 1) / slow.size * (payload / 2))
        lat = (slow.size - 1) * slow.latency if i == 0 else slow.latency
        assert lc.seconds == pytest.approx(
            lc.bytes_per_chip / slow.rate + lat)
    assert est.total_s == pytest.approx(
        sum(lc.seconds for lc in est.leg_charges))


def test_granted_lanes_scales_slow_legs_only():
    s = build_all_to_all(FAB3, SyncConfig(), (8, 4096))
    cm = CostModel(FAB3)
    base = cm.from_schedule(s)
    half = cm.from_schedule(s, granted_lanes=FAB3.slowest.lanes / 2)
    assert half.fast_s == pytest.approx(base.fast_s)
    assert half.slow_s == pytest.approx(2 * base.slow_s)


def test_mem_pricing_max_wire_memory():
    tight = MemPoolSpec.build(local_bw=1e9, local_channels=2)
    fab = FAB3.with_mem(tight)
    cm = CostModel(fab)
    s = build_all_to_all(fab, SyncConfig(), (8, 1 << 16))
    dry = cm.from_schedule(s)
    wet = cm.from_schedule(s, mem=True)
    assert wet.total_s > dry.total_s  # memory binds
    assert wet.fast_s == pytest.approx(dry.fast_s)


# ---------------------------------------------------------------------------
# simulator: per-destination flows
# ---------------------------------------------------------------------------


def test_sim_replays_per_destination_flows():
    fab = as_fabric(TwoTierTopology(num_pods=4, pod_shape=(2,)))
    s = all_to_all_from_axes(("data",), "pod", SyncConfig(chunks=2),
                             (8, 1 << 10), {"data": 2, "pod": 4},
                             tier_names=NAMES)
    est = CostModel(fab).from_schedule(s)
    res = simulate(fab, [Tenant("solo", s)])
    # 2 sub-flows x 3 destinations, all arbitrated
    assert len(res.slow_events("solo")) == 6
    assert abs(res.makespan - est.total_s) < 1e-9 * est.total_s
    # an all-reduce schedule still replays one flow per sub-flow
    from repro.core.schedule import schedule_from_axes
    ar = schedule_from_axes(("data",), "pod", SyncConfig(chunks=2,
                                                         pipeline=False),
                            (1 << 11,), 0, {"data": 2, "pod": 4},
                            tier_names=NAMES)
    res_ar = simulate(fab, [Tenant("solo", ar)])
    assert len(res_ar.slow_events("solo")) == 2


def test_per_destination_flows_split_the_lane_cap():
    """The ndest sub-flows of one slow chunk together hold ONE leg's lane
    budget: on a pool with spare capacity, an uncapped-by-max_lanes
    (max_lanes=None = 'no bursting') a2a tenant must still take its
    nominal priced time — the destinations must not each claim the full
    nominal cap and burst to ndest x the budget."""
    fab = as_fabric(TwoTierTopology(num_pods=4, pod_shape=(2,)))
    s = all_to_all_from_axes(("data",), "pod", SyncConfig(),
                             (8, 1 << 10), {"data": 2, "pod": 4},
                             tier_names=NAMES)
    est = CostModel(fab).from_schedule(s)
    # pool twice the tenant's nominal lanes: spare capacity to burst into
    pool = NicPool(lanes=2.0 * fab.slowest.lanes)
    res = simulate(fab, [Tenant("solo", s)], pool=pool)
    assert abs(res.makespan - est.total_s) < 1e-9 * est.total_s
    # an explicitly opportunistic tenant still bursts over the whole pool
    pool = NicPool(lanes=2.0 * fab.slowest.lanes)
    burst = simulate(fab, [Tenant("solo", s, max_lanes=pool.lanes)],
                     pool=pool)
    assert burst.makespan < res.makespan


def test_sim_contention_matches_granted_pricing():
    fab = as_fabric(TwoTierTopology(num_pods=4, pod_shape=(2,)))
    s = all_to_all_from_axes(("data",), "pod", SyncConfig(),
                             (8, 1 << 10), {"data": 2, "pod": 4},
                             tier_names=NAMES)
    cm = CostModel(fab)
    pool = NicPool(lanes=fab.slowest.lanes)
    res = simulate(fab, [Tenant(f"t{k}", s) for k in range(3)], pool=pool)
    est = cm.from_schedule(s, granted_lanes=pool.fair_share(3))
    assert abs(res.makespan - est.total_s) < 1e-9 * est.total_s


# ---------------------------------------------------------------------------
# planner
# ---------------------------------------------------------------------------


def test_plan_all_to_all_searches_chunks_and_staging():
    mem = MemPoolSpec.build(local_bw=50e9, local_channels=2, device_bw=25e9,
                            devices=2, device_latency=2e-6)
    pl = Planner(FAB3.with_mem(mem), min_chunk_numel=1 << 8)
    s = pl.plan_all_to_all((8, 1 << 12))
    assert s.kind == "all_to_all"
    assert s.staging in ("local", "pool")
    # the winner is the cheapest candidate it could have built itself
    cm = CostModel(FAB3.with_mem(mem))
    best = cm.from_schedule(s, mem=True).total_s
    for c in (1, 2, 4):
        for stg in ("local", "pool"):
            cand = build_all_to_all(FAB3.with_mem(mem), SyncConfig(chunks=c),
                                    (8, 1 << 12)).with_staging(stg)
            assert best <= cm.from_schedule(cand, mem=True).total_s + 1e-15


def test_plan_all_to_all_no_mem_fabric():
    pl = Planner(FAB3, min_chunk_numel=1 << 4)
    s = pl.plan_all_to_all((8, 256))
    assert s.kind == "all_to_all" and s.staging is None


# ---------------------------------------------------------------------------
# MoE dispatch threading
# ---------------------------------------------------------------------------


def test_moe_dispatch_schedule_matches_dispatch_buffer():
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp
    from repro.configs import get_smoke_arch
    from repro.models import layers as L

    arch = get_smoke_arch("deepseek-moe-16b")
    pl = Planner(FAB3, min_chunk_numel=1 << 8)
    n = FAB3.total_chips
    tokens = 128  # per member
    sched = L.moe_dispatch_schedule(arch, tokens, pl)
    moe = arch.moe
    C = L.moe_capacity(tokens, moe.top_k, moe.num_experts,
                       moe.capacity_factor)
    epm = max(moe.num_experts // n, 1)
    assert sched.shape == (n, epm * C * arch.d_model)

    p = L.init_moe(arch, jax.random.key(0), jnp.float32)
    x = jax.random.normal(jax.random.key(1), (2, 64, arch.d_model))
    y, _ = L.apply_moe(arch, p, x, dispatch_schedule=sched)
    assert y.shape == x.shape
    # capacity drift (different token count) is rejected loudly
    stale = L.moe_dispatch_schedule(arch, 4 * tokens, pl)
    with pytest.raises(ValueError, match="different dispatch buffer"):
        L.apply_moe(arch, p, x, dispatch_schedule=stale)
    # and so is an all-reduce schedule
    from repro.core.schedule import build_schedule
    with pytest.raises(ValueError, match="all_to_all"):
        L.apply_moe(arch, p, x, dispatch_schedule=build_schedule(
            FAB3, SyncConfig(), (8, 64)))


def test_moe_capacity_formula():
    import inspect

    from repro.models import layers as L
    assert L.moe_capacity(1024, 6, 64, 1.25) == 120
    assert L.moe_capacity(4, 2, 8, 1.0) == 4      # clamped to tokens
    assert L.moe_capacity(64, 1, 64, 1.0) == 8    # floor of 8
    # the dispatch must use THE shared formula, not an inline copy —
    # otherwise the apply_moe drift guard validates against the wrong C
    assert "moe_capacity(" in inspect.getsource(L._moe_dispatch)


def test_moe_dispatch_schedule_honors_planner_mesh_override():
    """The dispatch schedule must size its domain from the planner's own
    (possibly overridden) fast sizes, not the fabric description."""
    from repro.configs import get_smoke_arch
    from repro.models.layers import moe_capacity, moe_dispatch_schedule

    arch = get_smoke_arch("deepseek-moe-16b")  # E = 8
    # fabric says 2x2x2 = 8 members, the mesh override says 2*2 = 4
    pl = Planner(FAB3, fast_axes=(("data", 2),), min_chunk_numel=1 << 4)
    assert pl.domain_size == 4
    s = moe_dispatch_schedule(arch, 64, pl)
    assert s.shape[0] == 4
    C = moe_capacity(64, arch.moe.top_k, arch.moe.num_experts,
                     arch.moe.capacity_factor)
    assert s.numel == 4 * (8 // 4) * C * arch.d_model  # n * epm * C * d


def test_moe_dispatch_schedule_rejects_indivisible_experts():
    from repro.configs import get_smoke_arch
    from repro.models.layers import moe_dispatch_schedule

    arch = get_smoke_arch("deepseek-moe-16b")  # E = 8
    # 3-member domain: 8 % 3 != 0 — a floored plan would drop traffic
    fab = as_fabric(TwoTierTopology(num_pods=3, pod_shape=(1,)))
    pl = Planner(fab, min_chunk_numel=1 << 4)
    with pytest.raises(ValueError, match="E % members"):
        moe_dispatch_schedule(arch, 64, pl)


# ---------------------------------------------------------------------------
# the full battery (subprocess, like the other batteries)
# ---------------------------------------------------------------------------


def test_alltoall_battery():
    out = run_multi_device(os.path.join(HERE, "batteries",
                                        "alltoall_battery.py"))
    assert "ALL OK" in out
