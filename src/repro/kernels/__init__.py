"""Pallas TPU kernels for the paper's compute hot-spots (DESIGN.md §6).

Each kernel package ships kernel.py (pl.pallas_call + explicit BlockSpec
VMEM tiling), ops.py (model-layout wrapper) and ref.py (pure-jnp oracle
used by the allclose test sweeps).  Kernels compile for the TPU; they run
in Pallas interpret mode only where a caller passes ``interpret=True``,
and off the TPU without it Pallas refuses to lower them.
"""
from repro.kernels import flash_attention, mamba_scan, wkv6

__all__ = ["flash_attention", "mamba_scan", "wkv6"]
