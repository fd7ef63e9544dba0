"""Shared test helpers.

NOTE: no global XLA_FLAGS here (the brief requires tests to see 1 device).
Multi-device tests run battery scripts in a subprocess that sets
--xla_force_host_platform_device_count before importing jax.
"""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")


def run_multi_device(script_path: str, n_devices: int = 8, timeout: int = 600,
                     extra_env=None):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    # the child must never reach for an accelerator: on a TPU host its
    # parent (the pytest worker) may already hold the chip
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    if extra_env:
        env.update(extra_env)
    proc = subprocess.run([sys.executable, script_path], env=env,
                          capture_output=True, text=True, timeout=timeout)
    if proc.returncode != 0:
        raise AssertionError(
            f"multi-device battery failed (rc={proc.returncode}):\n"
            f"--- stdout ---\n{proc.stdout[-4000:]}\n"
            f"--- stderr ---\n{proc.stderr[-4000:]}")
    return proc.stdout
