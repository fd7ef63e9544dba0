"""Elastic-restart battery: a pod member dies mid-run, the job restarts
on the SHRUNK mesh, restores the last checkpoint (ZeRO-sharded state
re-laid-out via device_put target shardings), and the replayed loss
curve matches the no-failure run at every step both runs define — the
step-indexed data pipeline makes the global batch mesh-independent, so
only reduction order separates the trajectories.  A serve-side scenario
then kills most of the rack pool mid-fleet and asserts replanned
schedules (prefill rerouted onto the CXL shortcut) claw back goodput."""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import tempfile

import numpy as np

from repro.configs import get_smoke_arch
from repro.models import ModelSettings, build_model
from repro.runtime.train_loop import SimulatedFailure, Trainer, TrainerConfig
from repro.utils.jax_compat import make_mesh

ST = ModelSettings(param_dtype="float32", compute_dtype="float32",
                   remat="none", loss_chunk=16, max_seq=64)


class Shape:
    global_batch, seq_len = 8, 32
    name, kind = "t", "train"


STEPS, FAIL_AT = 8, 4
model = build_model(get_smoke_arch("qwen2-0.5b"), ST)
mesh_full = make_mesh((2, 2, 2), ("pod", "data", "model"))
mesh_shrunk = make_mesh((1, 2, 2), ("pod", "data", "model"))


def run(mesh, ckpt_dir, fail_at=None):
    cfg = TrainerConfig(steps=STEPS, lr=5e-3, warmup=2, log_every=0,
                        ckpt_every=2, ckpt_dir=ckpt_dir, mode="dfabric",
                        fail_at_step=fail_at, seed=7)
    return Trainer(model, mesh, Shape(), cfg).train()


tmp = tempfile.mkdtemp()

# uninterrupted reference on the full mesh
ref = run(mesh_full, os.path.join(tmp, "ref"))
ref_loss = {m["step"]: m["loss"] for m in ref["metrics"]}
assert len(ref_loss) == STEPS

# a pod member dies at step 4 (checkpoint lands just before the failure)
try:
    run(mesh_full, os.path.join(tmp, "ft"), fail_at=FAIL_AT)
    raise RuntimeError("injected failure did not fire")
except SimulatedFailure:
    pass

# restart on the SHRUNK mesh: restore + replay to completion
out = run(mesh_shrunk, os.path.join(tmp, "ft"))
assert out["step"] == STEPS
res_loss = {m["step"]: m["loss"] for m in out["metrics"]}
assert min(res_loss) == FAIL_AT, sorted(res_loss)  # resumed from step 4
for s, loss in sorted(res_loss.items()):
    np.testing.assert_allclose(loss, ref_loss[s], rtol=5e-3, atol=1e-4,
                               err_msg=f"step {s}")
print(f"elastic restart: {len(res_loss)} replayed steps on the shrunk "
      f"mesh match the reference (last loss {out['metrics'][-1]['loss']:.4f})")

# ---------------------------------------------------------------------------
# serve-side: mid-fleet lane death degrades goodput; replanned schedules
# (prefill path_split onto the CXL shortcut) recover part of it
# ---------------------------------------------------------------------------
from repro.core.mempool import MemPoolSpec  # noqa: E402
from repro.core.topology import (FabricSpec, HardwareSpec, Tier,  # noqa: E402
                                 cxl_shortcut_path)
from repro.serve_sim import (FleetConfig, WorkloadConfig,  # noqa: E402
                             generate_sessions, simulate_fleet)
from repro.sim.fabric_sim import lane_down  # noqa: E402

hw = HardwareSpec()
fab = FabricSpec(tiers=(
    Tier("ici", "data", 4, hw.ici_bw, hw.ici_latency),
    Tier("cxl", "host", 2, hw.cxl_bw, hw.cxl_latency),
    Tier("dcn", "pod", 4, hw.dcn_bw, hw.dcn_latency, lanes=2.0),
), hw=hw, mem=MemPoolSpec.build(local_bw=100e9, local_channels=2,
                                device_bw=25e9, devices=4,
                                device_latency=2e-6),
).with_paths(cxl_shortcut_path(lanes=2.0))

cfg = dict(slots=8, pool_lanes=4.0, bytes_per_token=16384.0,
           decode_sync_bytes=65536.0, kv_bytes_per_token=1024.0,
           step_compute_s=10e-6, kv_read_bw=20e9)
sessions = generate_sessions(WorkloadConfig(sessions=12, rate=200.0, seed=7))

healthy = simulate_fleet(fab, sessions, FleetConfig(**cfg))
faults = [lane_down(healthy.sim.makespan * 0.05, lanes=3.0)]
deg = simulate_fleet(fab, sessions, FleetConfig(**cfg), failures=faults)
assert deg.goodput_tok_s < healthy.goodput_tok_s, \
    (deg.goodput_tok_s, healthy.goodput_tok_s)
rep = simulate_fleet(
    fab, sessions,
    FleetConfig(prefill_path_split=(("cxl", 0.75),), **cfg),
    failures=faults)
assert rep.goodput_tok_s > deg.goodput_tok_s, \
    (rep.goodput_tok_s, deg.goodput_tok_s)
print(f"serve: goodput {healthy.goodput_tok_s:.0f} -> "
      f"{deg.goodput_tok_s:.0f} tok/s on lane death, replanned recovers "
      f"to {rep.goodput_tok_s:.0f} tok/s")

print("ALL OK")
