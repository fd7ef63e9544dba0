"""Multi-device CommSchedule battery (run via subprocess, 8 fake devices).

The overlapped-executor acceptance battery:

  * pipelined == sequential == flat ``lax.psum`` for 1/2/3-tier meshes x
    chunks in {1, 2, 4} x codec on/off (codec legs to tolerance, exact
    legs bitwise between pipelined and sequential);
  * the legs the executor lowers (``leg_log``) are IDENTICAL to the legs
    ``CostModel.from_schedule`` prices — walked from the same
    ``CommSchedule`` object;
  * build -> to_json -> from_json -> lower produces bitwise-identical
    results (the schedule JSON round-trip is lossless end-to-end);
  * a ``lane_offset``-rotated schedule (the NIC-pool stagger) lowers
    bitwise-identically to the unrotated one — the sub-flow ISSUE order
    changes, the payload reassembly by chunk index does not;
  * multi-path slow legs (``SyncConfig.path_split`` striping sub-flows
    across eth + the CXL shortcut) lower bitwise-identically at every
    split ratio — routing, like lane order, never touches the numerics —
    with the leg log still equal to the priced legs path-for-path, and
    path JSON round-tripping (old path-free JSON defaults to "eth").
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import CommSchedule, CostModel, SyncConfig
from repro.core.collectives import dfabric_all_reduce, lower_all_reduce
from repro.core.schedule import schedule_from_axes
from repro.core.topology import three_tier_fabric
from repro.utils import jax_compat

rng = np.random.default_rng(7)
x = rng.standard_normal((8, 1024)).astype(np.float32)
expect = x.sum(0)

# (mesh shape, mesh axes slowest-first, fast axes fastest-first, slow axis)
MESHES = [
    ((8,), ("data",), ("data",), None),                             # 1 tier
    ((2, 4), ("pod", "data"), ("data",), "pod"),                    # 2 tiers
    ((2, 2, 2), ("pod", "host", "data"), ("data", "host"), "pod"),  # 3 tiers
]


def run_allreduce(mesh, axes, fast, slow, cfg, xin=x):
    dp = P(axes if len(axes) > 1 else axes[0])

    def f(xs):
        out, _ = dfabric_all_reduce(xs.reshape(-1), fast, slow, cfg)
        return out

    g = jax.jit(jax_compat.shard_map(f, mesh=mesh, in_specs=dp,
                                     out_specs=P(), check_vma=False))
    return np.asarray(g(jax.device_put(xin, NamedSharding(mesh, dp))))


for shape, axes, fast, slow in MESHES:
    mesh = jax_compat.make_mesh(shape, axes)
    for chunks in (1, 2, 4):
        for codec in (None, "int8"):
            tol = 2e-2 if codec else 1e-6
            pipe = SyncConfig("hier_striped", chunks=chunks, codec=codec,
                              codec_block=128, pipeline=True)
            seq = replace(pipe, pipeline=False)
            out_p = run_allreduce(mesh, axes, fast, slow, pipe)
            out_s = run_allreduce(mesh, axes, fast, slow, seq)
            scale = np.max(np.abs(expect))
            err_p = np.max(np.abs(out_p - expect)) / scale
            err_s = np.max(np.abs(out_s - expect)) / scale
            assert err_p < tol, (axes, chunks, codec, "pipelined", err_p)
            assert err_s < tol, (axes, chunks, codec, "sequential", err_s)
            if codec is None:
                # exact legs: chunking must not change the sums at all
                d = np.max(np.abs(out_p - out_s)) / scale
                assert d < 1e-6, (axes, chunks, d)
    print(f"{len(axes)}-tier mesh {axes}: pipelined == sequential == psum "
          f"for chunks 1/2/4 x codec on/off OK")

# ---- the acceptance walk: executor leg log == priced leg list --------------
# (both consumers walk the SAME CommSchedule object)

AXES3 = ("pod", "host", "data")
mesh3 = jax_compat.make_mesh((2, 2, 2), AXES3)
fab3 = three_tier_fabric(num_pods=2, hosts_per_pod=2, chips_per_host=2)
sizes = {"data": 2, "host": 2, "pod": 2}
names = {"data": "ici", "host": "cxl", "pod": "dcn"}

for cfg, tol in ((SyncConfig("hier_striped", chunks=4, pipeline=True), 1e-6),
                 (SyncConfig("hier_striped", chunks=2, pipeline=False), 1e-6),
                 (SyncConfig("hier_striped", scatter_depth=1), 1e-6),
                 (SyncConfig("hier_striped", scatter_depth=1,
                             mid_codec="int8", codec_block=128), 2e-2),
                 (SyncConfig("hier_root", chunks=2), 1e-6),
                 (SyncConfig("flat"), 1e-6)):
    sched = schedule_from_axes(("data", "host"), "pod", cfg, (8192,), 0,
                               sizes, tier_names=names)
    est = CostModel(fab3).from_schedule(sched)
    priced = [lc.leg for lc in est.leg_charges]
    log = []

    def f(xs):
        out, _ = lower_all_reduce(sched, xs.reshape(-1), leg_log=log)
        return out

    g = jax.jit(jax_compat.shard_map(f, mesh=mesh3, in_specs=P(AXES3),
                                     out_specs=P(), check_vma=False))
    out = np.asarray(g(jax.device_put(x, NamedSharding(mesh3, P(AXES3)))))
    assert log == list(sched.legs) == priced, (cfg, log, priced)
    err = np.max(np.abs(out - expect)) / np.max(np.abs(expect))
    assert err < tol, (cfg, err)
    print(f"leg walk {sched.describe()}: executor == cost model "
          f"({len(log)} legs) OK")

# ---- JSON round-trip lowers identically ------------------------------------

cfg = SyncConfig("hier_striped", chunks=4, pipeline=True)
sched = schedule_from_axes(("data", "host"), "pod", cfg, (8192,), 0, sizes,
                           tier_names=names)
rt = CommSchedule.from_json(sched.to_json())
assert rt == sched
outs = []
for s in (sched, rt):
    def f(xs, s=s):
        out, _ = lower_all_reduce(s, xs.reshape(-1))
        return out
    g = jax.jit(jax_compat.shard_map(f, mesh=mesh3, in_specs=P(AXES3),
                                     out_specs=P(), check_vma=False))
    outs.append(np.asarray(g(jax.device_put(x, NamedSharding(mesh3, P(AXES3))))))
assert np.array_equal(outs[0], outs[1]), "round-tripped schedule diverged"
print("build -> to_json -> from_json -> lower: bitwise identical OK")

# ---- lane_offset rotation lowers identically (pipelined AND sequential) ----

for pipeline in (True, False):
    cfg = SyncConfig("hier_striped", chunks=4, pipeline=pipeline)
    base = schedule_from_axes(("data", "host"), "pod", cfg, (8192,), 0, sizes,
                              tier_names=names)
    ref = None
    for off in range(4):
        s = base.with_lane_offset(off)
        assert [l.index for l in s.slow_legs] == \
            [(j + off) % 4 for j in range(4)], (off, s.slow_legs)
        log = []

        def f(xs, s=s, log=log):
            out, _ = lower_all_reduce(s, xs.reshape(-1), leg_log=log)
            return out

        g = jax.jit(jax_compat.shard_map(f, mesh=mesh3, in_specs=P(AXES3),
                                         out_specs=P(), check_vma=False))
        out = np.asarray(g(jax.device_put(x, NamedSharding(mesh3, P(AXES3)))))
        assert log == list(s.legs), (off, log)  # issue order == leg order
        if ref is None:
            ref = out
        else:
            assert np.array_equal(out, ref), (pipeline, off)
    mode = "pipelined" if pipeline else "sequential"
    print(f"lane_offset 0..3 ({mode}): rotated issue order, bitwise "
          "identical results OK")

# ---- multi-path slow legs: routing is numerics-invariant -------------------
# (the executor reassembles by SlowChunk.index, so a schedule striping its
# sub-flows across eth + the CXL shortcut lowers BITWISE identically to the
# eth-only one at every split ratio, and both match a flat psum)

from repro.core.topology import cxl_shortcut_path

fab_mp = fab3.with_paths(cxl_shortcut_path())
cm_mp = CostModel(fab_mp)
for pipeline in (True, False):
    ref = None
    for frac in (0.0, 0.25, 0.5, 1.0):
        split = (("cxl", frac),) if frac > 0 else None
        cfg = SyncConfig("hier_striped", chunks=4, pipeline=pipeline,
                         path_split=split)
        sched = schedule_from_axes(("data", "host"), "pod", cfg, (8192,), 0,
                                   sizes, tier_names=names)
        paths = [l.path for l in sched.slow_legs]
        assert paths.count("cxl") == int(frac * 4 + 0.5), (frac, paths)
        est = cm_mp.from_schedule(sched)
        priced = [lc.leg for lc in est.leg_charges]
        log = []

        def f(xs, s=sched, log=log):
            out, _ = lower_all_reduce(s, xs.reshape(-1), leg_log=log)
            return out

        g = jax.jit(jax_compat.shard_map(f, mesh=mesh3, in_specs=P(AXES3),
                                         out_specs=P(), check_vma=False))
        out = np.asarray(g(jax.device_put(x, NamedSharding(mesh3, P(AXES3)))))
        # leg log == priced legs, paths included (same CommSchedule object)
        assert log == list(sched.legs) == priced, (frac, log, priced)
        assert [l.path for l in log if type(l).__name__ == "SlowChunk"] \
            == paths, (frac, paths)
        if 0.0 < frac < 1.0:  # a genuinely split leg prices BOTH routes
            assert dict(est.path_seconds).keys() == {"eth", "cxl"}, \
                est.path_seconds
        if ref is None:
            ref = out  # the eth-only baseline
        else:
            assert np.array_equal(out, ref), (pipeline, frac)
    err = np.max(np.abs(ref - expect)) / np.max(np.abs(expect))
    assert err < 1e-6, err
    mode = "pipelined" if pipeline else "sequential"
    print(f"multi-path split 0/.25/.5/1 ({mode}): bitwise identical across "
          "ratios, == psum, leg log == priced legs per path OK")

# ---- path JSON: round-trip preserves routes; old JSON defaults to eth ------

cfg = SyncConfig("hier_striped", chunks=4, path_split=(("cxl", 0.5),))
sched = schedule_from_axes(("data", "host"), "pod", cfg, (8192,), 0, sizes,
                           tier_names=names)
rt = CommSchedule.from_json(sched.to_json())
assert rt == sched
assert [l.path for l in rt.slow_legs] == [l.path for l in sched.slow_legs] \
    == ["eth", "eth", "cxl", "cxl"]
# pre-multipath plans: no "path" keys, no "path_split" — every sub-flow
# must come back as "eth" and the cfg as split-free
eth = schedule_from_axes(("data", "host"), "pod",
                         SyncConfig("hier_striped", chunks=4), (8192,), 0,
                         sizes, tier_names=names)
d = eth.to_dict()
assert not any("path" in ld for ld in d["legs"]), d["legs"]
del d["cfg"]["path_split"]  # what a pre-multipath writer emitted
old = CommSchedule.from_dict(d)
assert old == eth
assert all(l.path == "eth" for l in old.slow_legs)
print("path JSON: round-trip preserves routes, old JSON defaults to eth OK")

print("ALL OK")
