"""Multi-device N-tier battery (run via subprocess with 8 fake devices).

Asserts the recursive hierarchical collectives match their flat references
at every depth, on 1-, 2- and 3-tier DP meshes over the same 8 members:

  * ``dfabric_all_reduce`` == flat ``lax.psum`` for every strategy, chunk
    count and scatter depth (slow-leg codec to tolerance),
  * ``dfabric_reduce_scatter`` + ``dfabric_all_gather`` roundtrip == psum,
  * multi-stage ``dfabric_all_to_all`` == flat ``lax.all_to_all``,
  * the zero1 fused update on a 3-tier mesh == the paper-mode update.
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import SyncConfig, dfabric_all_reduce
from repro.core.collectives import dfabric_all_gather, dfabric_all_to_all, \
    dfabric_reduce_scatter
from repro.core.planner import Planner
from repro.core.topology import three_tier_fabric
from repro.optim import grad_sync
from repro.optim.adamw import AdamWConfig
from repro.optim.grad_sync import SyncSettings, sync_and_update
from repro.utils import jax_compat

rng = np.random.default_rng(0)
x = rng.standard_normal((8, 1024)).astype(np.float32)
expect = x.sum(0)

# (mesh shape, mesh axes slowest-first, fast axes fastest-first, slow axis)
MESHES = [
    ((8,), ("data",), ("data",), None),                       # 1 tier
    ((2, 4), ("pod", "data"), ("data",), "pod"),              # 2 tiers
    ((2, 2, 2), ("pod", "host", "data"), ("data", "host"), "pod"),  # 3 tiers
]

CONFIGS = [
    (SyncConfig("flat"), 1e-4),
    (SyncConfig("hier_root"), 1e-4),
    (SyncConfig("hier_striped"), 1e-4),
    (SyncConfig("hier_striped", chunks=4), 1e-4),
    (SyncConfig("hier_striped", scatter_depth=1), 1e-4),
    (SyncConfig("hier_striped", scatter_depth=0), 1e-4),
    (SyncConfig("hier_striped", codec="int8", codec_block=512), 2e-2),
]

for shape, axes, fast, slow in MESHES:
    mesh = jax_compat.make_mesh(shape, axes)
    dp = P(axes if len(axes) > 1 else axes[0])
    for cfg, tol in CONFIGS:
        def f(xs):
            out, _ = dfabric_all_reduce(xs.reshape(-1), fast, slow, cfg)
            return out
        g = jax.jit(jax_compat.shard_map(f, mesh=mesh, in_specs=dp,
                                         out_specs=P(), check_vma=False))
        out = np.asarray(g(jax.device_put(x, NamedSharding(mesh, dp))))
        err = np.max(np.abs(out - expect)) / np.max(np.abs(expect))
        assert err < tol, (axes, cfg.strategy, cfg.scatter_depth, err)
    print(f"allreduce {len(axes)}-tier mesh {axes}: all strategies OK")

    # reduce-scatter + all-gather roundtrip == psum (hier ownership order)
    def rs_ag(xs):
        s, _ = dfabric_reduce_scatter(xs.reshape(-1), fast, slow,
                                      SyncConfig("hier_striped"))
        return dfabric_all_gather(s, fast)
    g = jax.jit(jax_compat.shard_map(rs_ag, mesh=mesh, in_specs=dp,
                                     out_specs=P(), check_vma=False))
    out = np.asarray(g(jax.device_put(x, NamedSharding(mesh, dp))))
    err = np.max(np.abs(out - expect)) / np.max(np.abs(expect))
    assert err < 1e-4, (axes, err)
    print(f"rs+ag roundtrip {len(axes)}-tier: {err:.2e} OK")

    # hierarchical all-to-all == flat (domain rows ordered slow-major)
    xa = rng.standard_normal((8, 8, 3)).astype(np.float32)

    def a2a_flat(xl):
        return jax.lax.all_to_all(xl[0], axes, split_axis=0,
                                  concat_axis=0, tiled=True)[None]

    def a2a_hier(xl):
        return dfabric_all_to_all(xl[0], fast, slow)[None]

    outs = {}
    for nm, fn in (("flat", a2a_flat), ("hier", a2a_hier)):
        g = jax.jit(jax_compat.shard_map(
            fn, mesh=mesh, in_specs=P(axes, None, None),
            out_specs=P(axes, None, None), check_vma=False))
        xx = jax.device_put(xa, NamedSharding(mesh, P(axes, None, None)))
        outs[nm] = np.asarray(g(xx))
    assert np.array_equal(outs["flat"], outs["hier"]), axes
    print(f"all_to_all {len(axes)}-tier == flat OK")

# ---- partial-depth plans stripe (regression: the divisibility precheck
# must use the scatter-depth PREFIX product, not all fast tiers) -------------

AXES3 = ("pod", "host", "data")
mesh = jax_compat.make_mesh((2, 2, 2), AXES3)
xp = rng.standard_normal((8, 1026)).astype(np.float32)  # 1026 % 2 == 0, % 4 != 0

def ar_depth1(xs):
    out, _ = dfabric_all_reduce(xs.reshape(-1), ("data", "host"), "pod",
                                SyncConfig("hier_striped", scatter_depth=1))
    return out

g = jax.jit(jax_compat.shard_map(ar_depth1, mesh=mesh, in_specs=P(AXES3),
                                 out_specs=P(), check_vma=False))
out = np.asarray(g(jax.device_put(xp, NamedSharding(mesh, P(AXES3)))))
err = np.max(np.abs(out - xp.sum(0))) / np.max(np.abs(xp.sum(0)))
assert err < 1e-4, err
hlo = jax.jit(jax_compat.shard_map(ar_depth1, mesh=mesh, in_specs=P(AXES3),
                                   out_specs=P(), check_vma=False)
              ).lower(jax.ShapeDtypeStruct((8, 1026), jnp.float32)).as_text()
assert "reduce_scatter" in hlo or "psum_scatter" in hlo or \
    "reduce-scatter" in hlo, "depth-1 plan must actually reduce-scatter"
print(f"partial-depth (depth=1, %4!=0 payload) stripes + matches psum: "
      f"{err:.2e} OK")

# ---- zero1 == paper on the 3-tier mesh --------------------------------------

AXES3 = ("pod", "host", "data")
mesh = jax_compat.make_mesh((2, 2, 2), AXES3)
params = {"w": jnp.asarray(rng.standard_normal((8, 16)).astype(np.float32)),
          "b": jnp.asarray(rng.standard_normal((16,)).astype(np.float32))}
grads_global = {"w": rng.standard_normal((8, 8, 16)).astype(np.float32),
                "b": rng.standard_normal((8, 16)).astype(np.float32)}

fab = three_tier_fabric(num_pods=2, hosts_per_pod=2, chips_per_host=2)
shapes = {k: jax.ShapeDtypeStruct(v.shape, v.dtype) for k, v in params.items()}
plan = Planner(fab, strategy="hier_striped").plan(shapes, bucket_bytes=128)
for sec in plan.sections:
    assert sec.sync.scatter_depth == -1 or len(sec.leaf_paths) > 1, sec
opt_cfg = AdamWConfig(weight_decay=0.0, grad_clip=0.0)

outs = {}
for mode in ("zero1", "paper"):
    ss = SyncSettings(mode=mode, fast_axis="data", slow_axis="pod",
                      n_fast=4, n_slow=2, fast_axes=("data", "host"))
    state = grad_sync.init_sync_state(plan, shapes, ss)
    specs = grad_sync.sync_state_specs(plan, shapes, ss)

    def step(p, s, g):
        g = jax.tree.map(lambda a: a[0], g)  # strip the member dim
        np_, ns, m = sync_and_update(p, g, s, plan, ss, 1e-2, opt_cfg)
        return np_

    f = jax.jit(jax_compat.shard_map(
        step, mesh=mesh,
        in_specs=(P(), specs, {"w": P(AXES3, None, None),
                               "b": P(AXES3, None)}),
        out_specs=P(), check_vma=False))
    state = jax.device_put(state, jax.tree.map(
        lambda sp: NamedSharding(mesh, sp), specs))
    gput = {k: jax.device_put(v, NamedSharding(mesh, P(AXES3)))
            for k, v in grads_global.items()}
    outs[mode] = jax.tree.map(np.asarray, f(params, state, gput))

for k in params:
    d = np.max(np.abs(outs["zero1"][k] - outs["paper"][k]))
    assert d < 1e-5, (k, d)
print("3-tier zero1 == paper update OK")

print("ALL OK")
