"""All-to-all subsystem battery (run via subprocess, 8 fake devices).

The §6.2 shuffle / MoE-dispatch acceptance battery — all-to-all joins the
build / price / lower / simulate contract:

  * lowered hierarchical all-to-all (``lower_all_to_all`` walking a
    ``kind="all_to_all"`` :class:`CommSchedule`) is BITWISE equal to the
    flat ``lax.all_to_all`` over the joint (slowest, ..., fastest) domain
    on 1/2/3-tier meshes x slow-leg chunks 1/2/4, and ``lane_offset``
    rotations of the sub-flow issue order change nothing;
  * the legs the executor lowers (``leg_log``) are IDENTICAL to the legs
    ``CostModel.from_schedule`` prices — walked from the same schedule;
  * the schedule rides ``SyncPlan.to_json`` and round-trips losslessly
    (same object back, bitwise-identical lowering);
  * a single uncontended tenant's ``fabric_sim`` makespan equals
    ``ScheduleEstimate.total`` exactly (sequential — a2a schedules never
    pipeline), across chunk counts AND staging placements, with the slow
    sub-flows replayed as per-destination flows;
  * θ-way shuffle contention matches the ``granted_lanes`` /
    ``granted_mem_bw`` contention-aware pricing exactly.
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import itertools
import json

import jax
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import CommSchedule, CostModel, SyncConfig
from repro.core.collectives import dfabric_all_to_all, lower_all_to_all
from repro.core.mempool import MemPoolSpec
from repro.core.nicpool import NicPool
from repro.core.planner import Section, SyncPlan
from repro.core.schedule import all_to_all_from_axes
from repro.core.topology import (TwoTierTopology, as_fabric,
                                 fabric_from_mesh_sizes, three_tier_fabric)
from repro.sim.fabric_sim import Tenant, simulate
from repro.utils import jax_compat

EPS = 1e-9
NAMES = {"data": "ici", "host": "cxl", "pod": "dcn"}

rng = np.random.default_rng(11)
xa = rng.standard_normal((8, 8, 3)).astype(np.float32)

# (mesh shape, mesh axes slowest-first, fast axes fastest-first, slow axis,
#  pricing fabric) — all 8 members; the (4, 2) mesh exercises n_slow = 4,
# i.e. 3 per-destination sub-flows per slow chunk in the simulator
GRID = [
    ((8,), ("data",), ("data",), None,
     fabric_from_mesh_sizes({"data": 8})),
    ((2, 4), ("pod", "data"), ("data",), "pod",
     as_fabric(TwoTierTopology(num_pods=2, pod_shape=(4,)))),
    ((4, 2), ("pod", "data"), ("data",), "pod",
     as_fabric(TwoTierTopology(num_pods=4, pod_shape=(2,)))),
    ((2, 2, 2), ("pod", "host", "data"), ("data", "host"), "pod",
     three_tier_fabric(num_pods=2, hosts_per_pod=2, chips_per_host=2)),
]


def lower_on_mesh(mesh, axes, sched, leg_log=None):
    def f(xl):
        return lower_all_to_all(sched, xl[0], leg_log=leg_log)[None]

    g = jax.jit(jax_compat.shard_map(f, mesh=mesh,
                                     in_specs=P(axes, None, None),
                                     out_specs=P(axes, None, None),
                                     check_vma=False))
    xx = jax.device_put(xa, NamedSharding(mesh, P(axes, None, None)))
    return np.asarray(g(xx))


# ---------------------------------------------------------------------------
# 1. lowering: hierarchical == flat lax.all_to_all, bitwise, at every
#    depth x chunk count x lane offset; executor legs == priced legs
# ---------------------------------------------------------------------------

for shape, axes, fast, slow, fab in GRID:
    mesh = jax_compat.make_mesh(shape, axes)
    sizes = dict(zip(axes, shape))

    def a2a_flat(xl):
        return lax.all_to_all(xl[0], axes, split_axis=0, concat_axis=0,
                              tiled=True)[None]

    g = jax.jit(jax_compat.shard_map(a2a_flat, mesh=mesh,
                                     in_specs=P(axes, None, None),
                                     out_specs=P(axes, None, None),
                                     check_vma=False))
    flat = np.asarray(g(jax.device_put(
        xa, NamedSharding(mesh, P(axes, None, None)))))

    cm = CostModel(fab)
    for chunks in (1, 2, 4):
        sched = all_to_all_from_axes(fast, slow, SyncConfig(chunks=chunks),
                                     (8, 3), sizes, tier_names=NAMES)
        assert sched.kind == "all_to_all"
        C = max(len(sched.slow_legs), 1)
        for off in range(C):
            s = sched.with_lane_offset(off)
            log = []
            out = lower_on_mesh(mesh, axes, s, leg_log=log)
            est = cm.from_schedule(s)
            priced = [lc.leg for lc in est.leg_charges]
            assert log == list(s.legs) == priced, (axes, chunks, off)
            assert np.array_equal(out, flat), (axes, chunks, off)
        # the thin constructor (schedule built in-trace) lowers the same
        def f(xl):
            return dfabric_all_to_all(xl[0], fast, slow,
                                      SyncConfig(chunks=chunks))[None]
        g2 = jax.jit(jax_compat.shard_map(f, mesh=mesh,
                                          in_specs=P(axes, None, None),
                                          out_specs=P(axes, None, None),
                                          check_vma=False))
        out = np.asarray(g2(jax.device_put(
            xa, NamedSharding(mesh, P(axes, None, None)))))
        assert np.array_equal(out, flat), (axes, chunks, "in-trace")
    print(f"{len([a for a in axes])}-axis mesh {axes}: hier == flat "
          f"bitwise for chunks 1/2/4 x every lane offset OK")

# ---------------------------------------------------------------------------
# 2. SyncPlan.to_json round-trip: same schedule back, bitwise lowering
# ---------------------------------------------------------------------------

mesh3 = jax_compat.make_mesh((2, 2, 2), ("pod", "host", "data"))
sizes3 = {"data": 2, "host": 2, "pod": 2}
sched = all_to_all_from_axes(("data", "host"), "pod", SyncConfig(chunks=4),
                             (8, 3), sizes3,
                             tier_names=NAMES).with_lane_offset(1) \
    .with_staging("pool")
sec = Section(name="moe.dispatch", leaf_paths=("moe/dispatch",),
              numel=sched.numel, dtype="float32", scatter_dim=0,
              sync=sched.cfg, schedule=sched)
blob = json.loads(SyncPlan([sec]).to_json())
rt = CommSchedule.from_dict(blob[0]["schedule"])
assert rt == sched, "SyncPlan round-trip changed the schedule"
assert rt.kind == "all_to_all" and rt.lane_offset == 1 \
    and rt.staging == "pool"
a = lower_on_mesh(mesh3, ("pod", "host", "data"), sched)
b = lower_on_mesh(mesh3, ("pod", "host", "data"), rt)
assert np.array_equal(a, b), "round-tripped schedule lowers differently"
print("SyncPlan.to_json round-trip: schedule identical, lowering bitwise OK")

# ---------------------------------------------------------------------------
# 3. sim/price parity: 1/2/3 tiers x chunks 1/2/4 x staging local/pool
# ---------------------------------------------------------------------------

# a memory pool that BINDS (deliverable below the slow tier's demand),
# as in mempool_battery
tight = MemPoolSpec.build(local_bw=12e9, local_channels=2, device_bw=6e9,
                          devices=2, device_latency=2e-6)

checked = 0
for (shape, axes, fast, slow, fab0), chunks, stg in itertools.product(
        GRID, (1, 2, 4), ("local", "pool")):
    sizes = dict(zip(axes, shape))
    sched = all_to_all_from_axes(fast, slow, SyncConfig(chunks=chunks),
                                 (8, 1 << 12), sizes,
                                 tier_names=NAMES).with_staging(stg)
    fab = fab0.with_mem(tight)
    est = CostModel(fab).from_schedule(sched, mem=True)
    res = simulate(fab, [Tenant("solo", sched)])
    rel = abs(res.makespan - est.total_s) / max(est.total_s, 1e-30)
    assert rel < EPS, (axes, chunks, stg, est.total_s, res.makespan)
    # per-destination replay: one wire flow per remote slow-tier member
    # and per sub-flow
    n_slow = sizes.get(slow, 1) if slow else 1
    want = max(len(sched.slow_legs), 0) * max(n_slow - 1, 1) \
        if n_slow > 1 else 0
    assert len(res.slow_events("solo")) == want, (axes, chunks, want)
    # memory-free pricing == memory-free sim too
    est0 = CostModel(fab0).from_schedule(sched)
    res0 = simulate(fab0, [Tenant("solo", sched)])
    rel0 = abs(res0.makespan - est0.total_s) / max(est0.total_s, 1e-30)
    assert rel0 < EPS, (axes, chunks, stg)
    checked += 1
print(f"sim/price parity: {checked} all-to-all schedules exact "
      "(per-destination flows) OK")

# ---------------------------------------------------------------------------
# 4. θ-way shuffle contention == granted_lanes / granted_mem_bw pricing
# ---------------------------------------------------------------------------

fab4 = as_fabric(TwoTierTopology(num_pods=4, pod_shape=(2,)))
sizes4 = {"data": 2, "pod": 4}
sched = all_to_all_from_axes(("data",), "pod", SyncConfig(chunks=2),
                             (8, 1 << 12), sizes4, tier_names=NAMES)
cm = CostModel(fab4)
for theta in (2, 4, 8):
    pool = NicPool(lanes=fab4.slowest.lanes)
    res = simulate(fab4, [Tenant(f"t{k}", sched) for k in range(theta)],
                   pool=pool)
    est = cm.from_schedule(sched, granted_lanes=pool.fair_share(theta))
    rel = abs(res.makespan - est.total_s) / est.total_s
    assert rel < EPS, (theta, res.makespan, est.total_s)
    assert est.total_s > cm.from_schedule(sched).total_s
print("contention: sim == granted-lanes pricing for theta in 2/4/8 OK")

fabm = fab4.with_mem(tight)
cmm = CostModel(fabm)
for stg in ("local", "pool"):
    s = sched.with_staging(stg)
    for theta in (2, 4):
        pool = NicPool(lanes=fabm.slowest.lanes)
        res = simulate(fabm, [Tenant(f"t{k}", s) for k in range(theta)],
                       pool=pool)
        est = cmm.from_schedule(
            s, mem=True, granted_lanes=pool.fair_share(theta),
            granted_mem_bw=tight.deliverable_bw(stg) / theta)
        rel = abs(res.makespan - est.total_s) / est.total_s
        assert rel < EPS, (stg, theta, res.makespan, est.total_s)
print("contention: sim == granted-mem pricing for both stagings OK")

# ---------------------------------------------------------------------------
# 5. skewed (dest_sizes) schedules: the skew is a wire/pricing annotation,
#    so the lowering stays BITWISE the flat all_to_all; sim == price holds
#    at the true per-destination sizes; the annotation rides SyncPlan JSON
# ---------------------------------------------------------------------------

skew_w = rng.uniform(0.0, 8.0, size=8)
skew_w[0] = 24.0  # one hot destination row

for shape, axes, fast, slow, fab0 in GRID:
    mesh = jax_compat.make_mesh(shape, axes)
    sizes = dict(zip(axes, shape))

    def a2a_flat(xl):
        return lax.all_to_all(xl[0], axes, split_axis=0, concat_axis=0,
                              tiled=True)[None]

    g = jax.jit(jax_compat.shard_map(a2a_flat, mesh=mesh,
                                     in_specs=P(axes, None, None),
                                     out_specs=P(axes, None, None),
                                     check_vma=False))
    flat = np.asarray(g(jax.device_put(
        xa, NamedSharding(mesh, P(axes, None, None)))))
    for chunks in (1, 2):
        ds = [float(8 * 3 * 4) * w / skew_w.sum() for w in skew_w]
        s = all_to_all_from_axes(fast, slow, SyncConfig(chunks=chunks),
                                 (8, 3), sizes, tier_names=NAMES,
                                 dest_sizes=ds)
        out = lower_on_mesh(mesh, axes, s)
        assert np.array_equal(out, flat), ("skewed lowering", axes, chunks)
print("skewed schedules lower bitwise == flat on every mesh OK")

checked = 0
for (shape, axes, fast, slow, fab0), chunks, stg in itertools.product(
        GRID, (1, 2), ("local", "pool")):
    sizes = dict(zip(axes, shape))
    payload = float(8 * (1 << 12) * 4)
    ds = [payload * w / skew_w.sum() for w in skew_w]
    s = all_to_all_from_axes(fast, slow, SyncConfig(chunks=chunks),
                             (8, 1 << 12), sizes, tier_names=NAMES,
                             dest_sizes=ds).with_staging(stg)
    fab = fab0.with_mem(tight)
    est = CostModel(fab).from_schedule(s, mem=True)
    res = simulate(fab, [Tenant("solo", s)])
    rel = abs(res.makespan - est.total_s) / max(est.total_s, 1e-30)
    assert rel < EPS, ("skewed mem", axes, chunks, stg, rel)
    est0 = CostModel(fab0).from_schedule(s)
    res0 = simulate(fab0, [Tenant("solo", s)])
    rel0 = abs(res0.makespan - est0.total_s) / max(est0.total_s, 1e-30)
    assert rel0 < EPS, ("skewed", axes, chunks, stg, rel0)
    # the incast bound never prices below the uniform schedule
    u = all_to_all_from_axes(fast, slow, SyncConfig(chunks=chunks),
                             (8, 1 << 12), sizes, tier_names=NAMES) \
        .with_staging(stg)
    assert est0.total_s >= CostModel(fab0).from_schedule(u).total_s - 1e-30
    checked += 1
print(f"skewed sim/price parity: {checked} schedules exact OK")

mesh3 = jax_compat.make_mesh((2, 2, 2), ("pod", "host", "data"))
ds = [float(8 * 3 * 4) * w / skew_w.sum() for w in skew_w]
s = all_to_all_from_axes(("data", "host"), "pod", SyncConfig(chunks=2),
                         (8, 3), sizes3, tier_names=NAMES,
                         dest_sizes=ds).with_staging("pool")
sec = Section(name="moe.dispatch", leaf_paths=("moe/dispatch",),
              numel=s.numel, dtype="float32", scatter_dim=0,
              sync=s.cfg, schedule=s)
blob = json.loads(SyncPlan([sec]).to_json())
rt = CommSchedule.from_dict(blob[0]["schedule"])
assert rt == s, "skewed SyncPlan round-trip changed the schedule"
assert all(l.dest_sizes is not None for l in rt.legs)
a = lower_on_mesh(mesh3, ("pod", "host", "data"), s)
b = lower_on_mesh(mesh3, ("pod", "host", "data"), rt)
assert np.array_equal(a, b), "round-tripped skewed schedule lowers differently"
print("skewed SyncPlan.to_json round-trip: dest_sizes survive, "
      "lowering bitwise OK")

print("ALL OK")
