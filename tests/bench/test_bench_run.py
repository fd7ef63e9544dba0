"""The benchmark's command refuses to run where it cannot measure."""
import os
import shutil
import subprocess
import sys

from bench_fixtures import REPO

ARGS = ["--workload", "qwen2-0.5b.train.s2048", "--seed", "3000000017",
        "--seconds", "1", "--trace", "0"]


def _run(root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=root,
                          env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_non_zero_and_prints_no_result():
    proc = _run(REPO)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "TPU" in proc.stderr


def test_without_the_program_exits_non_zero(tmp_path):
    """A checkout that holds only BENCHMARK.json and the benchmark's own
    directories has nothing to measure."""
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    for p in ("bench", "tests/bench"):
        shutil.copytree(REPO / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
