"""Multi-device training battery: on a (2,2,2) mesh, train smoke archs for
a few steps in every mode and assert the loss decreases; lower a small
dry-run cell to validate the launch path end-to-end."""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_smoke_arch
from repro.core.topology import TwoTierTopology
from repro.models import ModelSettings, build_model
from repro.runtime.train_loop import Trainer, TrainerConfig
from repro.utils.jax_compat import make_mesh

mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
topo = TwoTierTopology(num_pods=2, pod_shape=(2, 2))


class Shape:
    global_batch, seq_len = 8, 32
    name, kind = "t", "train"


ST = ModelSettings(param_dtype="float32", compute_dtype="float32",
                   remat="none", loss_chunk=16, max_seq=64)

# dense arch through all three modes; moe + hybrid through dfabric
runs = [
    ("qwen3-1.7b", dict(mode="dfabric", zero1=True, codec=None)),
    ("qwen3-1.7b", dict(mode="dfabric", zero1=False, codec="int8")),
    ("qwen3-1.7b", dict(mode="gspmd")),
    ("deepseek-moe-16b", dict(mode="dfabric", zero1=True)),
    ("jamba-1.5-large-398b", dict(mode="dfabric", zero1=True)),
    ("whisper-medium", dict(mode="dfabric", zero1=True)),
]
for name, kw in runs:
    model = build_model(get_smoke_arch(name), ST)
    cfg = TrainerConfig(steps=8, lr=8e-3, warmup=2, log_every=0, seed=3, **kw)
    tr = Trainer(model, mesh, Shape(), cfg, topo=topo)
    out = tr.train()
    losses = [m["loss"] for m in out["metrics"]]
    assert all(np.isfinite(l) for l in losses), (name, kw, losses)
    assert losses[-1] < losses[0], (name, kw, losses[0], losses[-1])
    print(f"{name} {kw}: {losses[0]:.3f} -> {losses[-1]:.3f} OK")

# 3-tier fabric end-to-end: (pod, host, data, model) mesh; the Trainer
# derives an N-tier FabricSpec from the "host" axis and the planner's
# per-tier scatter depths flow through grad_sync inside the step
mesh3 = make_mesh((2, 2, 2, 1), ("pod", "host", "data", "model"))
model = build_model(get_smoke_arch("qwen2-0.5b"), ST)
cfg = TrainerConfig(steps=8, lr=8e-3, warmup=2, log_every=0, seed=3,
                    mode="dfabric", zero1=True)
tr = Trainer(model, mesh3, Shape(), cfg)
from repro.core.topology import FabricSpec  # noqa: E402
assert isinstance(tr.topo, FabricSpec) and tr.topo.depth == 3
assert tr.ss.fast_axes == ("data", "host") and tr.ss.n_fast == 4
assert any(s.sync.scatter_depth != 0 for s in tr.plan.sections)
out = tr.train()
losses = [m["loss"] for m in out["metrics"]]
assert all(np.isfinite(l) for l in losses), losses
assert losses[-1] < losses[0], (losses[0], losses[-1])
print(f"qwen2-0.5b 3-tier (2x2x2x1): {losses[0]:.3f} -> {losses[-1]:.3f} OK")

# microbatched gradient accumulation == single batch (same data)
model = build_model(get_smoke_arch("qwen2-0.5b"), ST)
for mb in (1, 2):
    cfg = TrainerConfig(steps=3, lr=5e-3, warmup=1, log_every=0, seed=11,
                        mode="dfabric", microbatches=mb)
    tr = Trainer(model, mesh, Shape(), cfg, topo=topo)
    out = tr.train()
    print(f"microbatches={mb}: loss {out['metrics'][-1]['loss']:.6f}")

# tiny dry-run-style lowering through the cells path on the test mesh
from repro.launch.cells import _batch_sds  # noqa: E402
from repro.models.sharding import MeshInfo  # noqa: E402
from repro.roofline.hlo_parse import parse_collectives  # noqa: E402
from repro.runtime.train_loop import make_dfabric_train_step, make_sync_plan, mesh_info  # noqa: E402
from repro.optim.adamw import AdamWConfig, cosine_schedule  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

model = build_model(get_smoke_arch("qwen3-1.7b"), ST)
plan, ss = make_sync_plan(model, mesh, topo)
step_fn, init_state, state_sharding = make_dfabric_train_step(
    model, mesh, plan, ss, AdamWConfig(), cosine_schedule(1e-3, 2, 10),
    donate=False)
pshapes = model.param_shapes()
mi = mesh_info(mesh)
pspecs = model.param_specs(mi)
params = jax.tree.map(
    lambda sds, sp: jax.ShapeDtypeStruct(sds.shape, sds.dtype,
                                         sharding=NamedSharding(mesh, sp)),
    pshapes, pspecs, is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
sshapes = jax.eval_shape(init_state)
sync_state = jax.tree.map(
    lambda sds, sh: jax.ShapeDtypeStruct(sds.shape, sds.dtype, sharding=sh),
    sshapes, state_sharding, is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


class Sh2:
    global_batch, seq_len = 8, 32
    name, kind = "t", "train"


batch = {
    "tokens": jax.ShapeDtypeStruct((8, 32), jnp.int32,
                                   sharding=NamedSharding(mesh, P(("pod", "data"), None))),
    "labels": jax.ShapeDtypeStruct((8, 32), jnp.int32,
                                   sharding=NamedSharding(mesh, P(("pod", "data"), None))),
}
lowered = step_fn.lower(params, sync_state, batch, jnp.int32(0))
compiled = lowered.compile()
coll = parse_collectives(compiled.as_text(), chips_per_pod=4)
assert coll.wire_bytes("dcn") > 0, "pod-axis (DCN) collectives must exist"
assert coll.wire_bytes("ici") > 0
print(f"dry-run lowering: ici={coll.wire_bytes('ici')/2**20:.2f}MiB "
      f"dcn={coll.wire_bytes('dcn')/2**20:.2f}MiB OK")

print("ALL OK")
