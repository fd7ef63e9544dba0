"""``correct`` at a size a CPU test holds: a sound run of the timed path is
correct, the control is not, and each fault the cells can have, planted in
the timed path, makes a whole run read not correct."""
import json
import os
import subprocess
import sys

import pytest

from bench_fixtures import REPO, tiny_cell

SEED = 3_000_000_019  # larger than 32 signed bits hold


def _run(cell, tmp_path, hook=None, seconds=0.3):
    import time

    from bench import cell as bcell

    return bcell.run(cell, SEED, seconds, False, time.perf_counter(),
                     tmp_path / "out", hook=hook)


@pytest.fixture
def cell(tmp_path):
    return tiny_cell(tmp_path)


def test_sound_run_is_correct(cell, tmp_path):
    res = _run(cell, tmp_path)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["metrics"]["tokens_per_s"]["value"] > 0
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == {"loss_gap", "grad_gap", "update_gap", "grad_err",
                                  "batch_mismatch", "repeated_rows"}


def test_step_that_returns_its_state_unchanged(cell, tmp_path):
    import jax
    import jax.numpy as jnp

    def hook(s):
        step = s.trainer.step_fn

        def frozen(params, opt, batch, i):
            # the real step on copies (it donates its inputs) for the metrics
            copy = jax.tree.map(jnp.copy, (params, opt))
            _, _, metrics = step(*copy, batch, i)
            return params, opt, metrics
        s.trainer.step_fn = frozen

    res = _run(cell, tmp_path, hook)
    assert not res["correct"]
    assert res["checks"]["update_gap"]["value"] == pytest.approx(1.0)


def test_half_of_the_batch_left_out(cell, tmp_path):
    def hook(s):
        loss = s.trainer.model.loss
        s.trainer.model.loss = lambda p, b: loss(
            p, {k: v[: v.shape[0] // 2] for k, v in b.items()})

    res = _run(cell, tmp_path, hook)
    assert not res["correct"], res["checks"]


def test_control_in_lower_precision_is_not_correct(cell):
    """The reference computed in float8, put in the program's place, fails
    the cell's limits."""
    from bench import cell as bcell, check
    from bench.reference import train as rtrain

    batches = bcell.reference_batches(cell, SEED)
    opt = cell.workload["optimizer"]
    ref = rtrain.run(cell.cfg, opt, SEED, batches)
    control = rtrain.run(cell.cfg, opt, SEED, batches, precision="fp8")
    ok, checks = check.judge(check.gaps(control, ref), cell.workload["limits"])
    assert not ok, checks


def test_exchange_between_chips_left_out(tmp_path):
    """On four CPU devices, (pod, data) = (2, 2): a sound run is correct,
    and one whose gradient reduce-scatter exchanges nothing is not."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, str(REPO / "tests/bench/dp_fault_child.py"), str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["sound"], out
    assert not out["no_exchange"], out
