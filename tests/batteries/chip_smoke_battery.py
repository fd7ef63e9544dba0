"""``chip_smoke.py --chips 4``'s phase at smoke width on 4 forced CPU
devices: dfabric and gspmd training on a (2, 2, 1) mesh must agree."""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import importlib.util  # noqa: E402

from repro.configs.base import get_smoke_arch  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
spec = importlib.util.spec_from_file_location(
    "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
cs = importlib.util.module_from_spec(spec)
spec.loader.exec_module(cs)

curves = cs.dp_phase(get_smoke_arch(cs.ARCH), batch=4, seq=32, steps=4)
assert len(curves["dfabric"]) == len(curves["gspmd"]) == 4
print("ALL OK")
