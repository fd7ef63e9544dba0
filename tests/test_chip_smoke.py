"""``chip_smoke.py`` at smoke width on the CPU.

Every phase function runs here on the smoke config, kernels in interpret
mode passed explicitly from this file; ``main()`` itself must refuse a
backend without a TPU.  The chip run at published widths is
``python chip_smoke.py`` on the chip.
"""
import importlib.util
import os

import jax
import pytest

from conftest import REPO, run_multi_device

HERE = os.path.dirname(os.path.abspath(__file__))

_spec = importlib.util.spec_from_file_location(
    "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
cs = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(cs)

SMOKE_KERNEL_WIDTHS = {
    "flash_attention": dict(B=1, H=4, KV=2, S=128, hd=32),
    "wkv6": dict(B=1, H=2, S=64, hd=16),
    "mamba_scan": dict(B=1, S=64, di=32, ds=8),
}


def _smoke_arch():
    from repro.configs.base import get_smoke_arch
    return get_smoke_arch(cs.ARCH)


def test_main_refuses_a_backend_without_tpu():
    assert jax.default_backend() != "tpu"
    with pytest.raises(SystemExit) as e:
        cs.main([])
    assert e.value.code not in (0, None)


def test_train_phase_smoke():
    out = cs.train_phase(_smoke_arch(), batch=2, seq=32, steps=5)
    assert out["last_loss"] < out["first_loss"]
    assert out["steady_step_s"] > 0


def test_kernel_phase_smoke_interpret():
    errs = cs.kernel_phase(SMOKE_KERNEL_WIDTHS, interpret=True)
    assert set(errs) == {"flash_attention[bfloat16]",
                         "flash_attention[float32]", "wkv6.y", "wkv6.state",
                         "mamba_scan.y", "mamba_scan.state"}


def test_decode_phase_smoke():
    out = cs.decode_phase(_smoke_arch(), requests=5, max_new=4, slots=2,
                          max_seq=32)
    assert out["tokens"] == 5 * 4


@pytest.mark.parametrize("losses", [[5.0, float("nan"), 4.0], [5.0, 5.1, 5.2]])
def test_check_losses_rejects(losses):
    with pytest.raises(AssertionError):
        cs.check_losses(losses, "t")


def test_dp_phase_smoke_four_devices():
    out = run_multi_device(os.path.join(HERE, "batteries",
                                        "chip_smoke_battery.py"), n_devices=4)
    assert "ALL OK" in out


def test_compile_cache_honours_env(monkeypatch, tmp_path):
    from repro.launch.compile_cache import use_compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
