"""Multi-device collective battery (run via subprocess with 8 fake devices).

Asserts, on a (2 pods x 2 data x 2 model) mesh:
  * every dfabric_all_reduce strategy == flat psum (to codec tolerance),
  * explicit ppermute ring all-reduce == psum,
  * the zero1 fused path produces the same updated params as the paper
    path (no codec),
  * error feedback makes compressed sync unbiased over repeats.
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import SyncConfig, dfabric_all_reduce, ring_all_reduce
from repro.core.planner import Planner
from repro.core.topology import TwoTierTopology
from repro.models.sharding import MeshInfo
from repro.optim import grad_sync
from repro.optim.adamw import AdamWConfig
from repro.optim.grad_sync import SyncSettings, sync_and_update
from repro.utils import jax_compat
from repro.utils.trees import tree_paths

mesh = jax_compat.make_mesh((2, 2, 2), ("pod", "data", "model"))

rng = np.random.default_rng(0)
x = rng.standard_normal((4, 4096)).astype(np.float32)  # 4 = pod x data members
expect = x.sum(0)


def run_ar(cfg):
    def f(xs):
        out, _ = dfabric_all_reduce(xs.reshape(-1), "data", "pod", cfg)
        return out
    g = jax.jit(jax_compat.shard_map(f, mesh=mesh, in_specs=P(("pod", "data")),
                              out_specs=P(), check_vma=False))
    xx = jax.device_put(x, NamedSharding(mesh, P(("pod", "data"))))
    return np.asarray(g(xx))


for cfg, tol in [
    (SyncConfig("flat"), 1e-4),
    (SyncConfig("hier_root"), 1e-4),
    (SyncConfig("hier_striped"), 1e-4),
    (SyncConfig("hier_striped", chunks=4), 1e-4),
    (SyncConfig("hier_striped", codec="int8", codec_block=512), 2e-2),
    (SyncConfig("hier_striped", codec="topk", codec_k_frac=1.0), 1e-4),
]:
    out = run_ar(cfg)
    err = np.max(np.abs(out - expect)) / np.max(np.abs(expect))
    assert err < tol, (cfg, err)
    print(f"allreduce {cfg.strategy} chunks={cfg.chunks} codec={cfg.codec}: {err:.2e} OK")

# ring == psum (over data axis within each pod)
def fr(xs):
    return ring_all_reduce(xs.reshape(-1), "data", 2)
g = jax.jit(jax_compat.shard_map(fr, mesh=mesh, in_specs=P(("pod", "data")),
                          out_specs=P("pod"), check_vma=False))
xx = jax.device_put(x, NamedSharding(mesh, P(("pod", "data"))))
out = np.asarray(g(xx)).reshape(2, 4096)
exp2 = x.reshape(2, 2, 4096).sum(1)  # per-pod reduce over the data axis
assert np.allclose(out, exp2, rtol=1e-5, atol=1e-4), np.abs(out - exp2).max()
print("ring_all_reduce OK")

# ---- zero1 vs paper equivalence on a toy param tree -------------------------
params = {"w": jnp.asarray(rng.standard_normal((8, 16)).astype(np.float32)),
          "b": jnp.asarray(rng.standard_normal((16,)).astype(np.float32))}
grads_global = {"w": rng.standard_normal((4, 8, 16)).astype(np.float32),
                "b": rng.standard_normal((4, 16)).astype(np.float32)}

topo = TwoTierTopology(num_pods=2, pod_shape=(2, 2))
shapes = {k: jax.ShapeDtypeStruct(v.shape, v.dtype) for k, v in params.items()}
planner = Planner(topo, fast_axis_size=2, strategy="hier_striped")
plan = planner.plan(shapes, bucket_bytes=128)  # w becomes its own section
opt_cfg = AdamWConfig(weight_decay=0.0, grad_clip=0.0)


outs = {}
for mode in ("zero1", "paper"):
    ss = SyncSettings(mode=mode, fast_axis="data", slow_axis="pod", n_fast=2, n_slow=2)
    state = grad_sync.init_sync_state(plan, shapes, ss)
    specs = grad_sync.sync_state_specs(plan, shapes, ss)

    def step(p, s, g):
        g = jax.tree.map(lambda a: a[0], g)  # strip the member dim
        np_, ns, m = sync_and_update(p, g, s, plan, ss, 1e-2, opt_cfg)
        return np_
    # all mesh axes manual ("model" is unused here)
    f = jax.jit(jax_compat.shard_map(
        step, mesh=mesh,
        in_specs=(P(), specs,
                  {"w": P(("pod", "data"), None, None),
                   "b": P(("pod", "data"), None)}),
        out_specs=P(), check_vma=False))
    state = jax.device_put(state, jax.tree.map(lambda sp: NamedSharding(mesh, sp), specs))
    gput = {k: jax.device_put(v, NamedSharding(mesh, P(("pod", "data"))))
            for k, v in grads_global.items()}
    outs[mode] = jax.tree.map(np.asarray, f(params, state, gput))

for k in params:
    d = np.max(np.abs(outs["zero1"][k] - outs["paper"][k]))
    assert d < 1e-5, (k, d)
print("zero1 == paper update OK")

# ---- two-stage hierarchical all-to-all == flat all-to-all -------------------
from repro.core.collectives import dfabric_all_to_all

xa = np.arange(4 * 4 * 3, dtype=np.float32).reshape(4, 4, 3)  # 4 = pod x data members


def a2a_flat(xl):
    return jax.lax.all_to_all(xl[0], ("pod", "data"), split_axis=0,
                              concat_axis=0, tiled=True)[None]


def a2a_hier(xl):
    return dfabric_all_to_all(xl[0], "data", "pod")[None]


outs_a2a = {}
for nm, fn in (("flat", a2a_flat), ("hier", a2a_hier)):
    g = jax.jit(jax_compat.shard_map(fn, mesh=mesh, in_specs=P(("pod", "data"), None, None),
                              out_specs=P(("pod", "data"), None, None),
                              check_vma=False))
    xx = jax.device_put(xa, NamedSharding(mesh, P(("pod", "data"), None, None)))
    outs_a2a[nm] = np.asarray(g(xx))
assert np.array_equal(outs_a2a["flat"], outs_a2a["hier"])
print("hierarchical all_to_all == flat OK")

print("ALL OK")
