"""Child of ``test_ntier.test_sync_plan_names_only_mesh_axes`` (8 forced
CPU devices): plans the smoke ``qwen2-0.5b`` with ``make_sync_plan`` on
small (pod, data, model) meshes and prints, as one JSON line, each mesh's
axes and the axes each Section's schedule names."""
import json
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax

from repro.configs import get_smoke_arch
from repro.models import ModelSettings, build_model
from repro.runtime.train_loop import make_sync_plan
from repro.utils.jax_compat import make_mesh

MESHES = ((1, 2, 1), (1, 4, 1), (1, 2, 2), (2, 2, 1))

model = build_model(get_smoke_arch("qwen2-0.5b"),
                    ModelSettings(param_dtype="float32",
                                  compute_dtype="float32", remat="none",
                                  loss_chunk=16, max_seq=64))
out = {}
for shape in MESHES:
    n = shape[0] * shape[1] * shape[2]
    mesh = make_mesh(shape, ("pod", "data", "model"),
                     devices=jax.devices()[:n])
    plan, _ = make_sync_plan(model, mesh)
    out[str(list(shape))] = {
        "mesh_axes": list(mesh.axis_names),
        "sections": {s.name: list(s.schedule.axes) for s in plan.sections}}
print(json.dumps(out))
