"""Memory-pool analogues: donation, staging, ZeRO sharding, host offload.

(Formerly ``repro.core.memory_pool`` — renamed to resolve the collision
with :mod:`repro.core.mempool`, the simulated/priced memory-pool arbiter;
that path survives as a deprecated re-export shim.)

The paper's memory pool (§4.1) exists so the NIC pool can DMA at its full
aggregate rate, and so CNs can consume received data in place
(pass-by-reference, §4.3).  The TPU-native mapping:

  * **pass-by-reference** → buffer donation: updated params/opt-state reuse
    the incoming buffers; no copy of the old state survives.  Provided as
    :func:`donated_jit` and used by every train step.
  * **aggregate-HBM absorption** → ZeRO sharding of the optimizer state over
    the ICI axis (each chip's HBM holds 1/N of the state — the pool), with
    the fused reduce-scatter -> update -> all-gather path in
    ``optim.grad_sync``.
  * **added memory devices** → host DRAM offload of opt state via JAX
    memory kinds (``pinned_host``), gated on the backend listing that
    kind among the device's addressable memories.
  * **Sections/Buffers** → the planner's bucketing (see planner.py).
"""
from __future__ import annotations

import functools
from typing import Any, Optional, Sequence

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P


def donated_jit(fn=None, *, donate_argnums: Sequence[int] = (0, 1), **jit_kw):
    """jit with donated carry arguments — the pass-by-reference train step.

    The params/opt-state buffers of step *t* are donated to step *t+1*;
    nothing is passed by value.
    """
    if fn is None:
        return functools.partial(donated_jit, donate_argnums=donate_argnums, **jit_kw)
    return jax.jit(fn, donate_argnums=donate_argnums, **jit_kw)


def host_memory_kind_available() -> bool:
    """True if the backend supports pinned_host memory placement."""
    kinds = {m.kind for m in jax.devices()[0].addressable_memories()}
    return "pinned_host" in kinds


def with_memory_kind(sharding: NamedSharding, kind: str) -> NamedSharding:
    return sharding.with_memory_kind(kind)


def offload_sharding(mesh, spec: P, *, offload: bool) -> NamedSharding:
    """Sharding for optimizer state; placed in host DRAM when requested and
    supported (the paper's 'additional memory devices')."""
    s = NamedSharding(mesh, spec)
    if offload and host_memory_kind_available():
        return s.with_memory_kind("pinned_host")
    return s


class StagingBuffers:
    """Double-buffered host->device staging — the RX-queue analogue.

    The data pipeline writes batch t+1 into the idle buffer while step t
    consumes the active one; mirrors the paper's virt_queue RX flow where
    the NIC pool DMAs ahead of the CN's consumption.
    """

    def __init__(self, sharding: NamedSharding, n_slots: int = 2):
        self.sharding = sharding
        self.n_slots = n_slots
        self._slots: list = [None] * n_slots
        self._next = 0

    def put(self, host_batch: Any) -> Any:
        slot = self._next
        self._next = (self._next + 1) % self.n_slots
        dev = jax.device_put(host_batch, self.sharding)
        self._slots[slot] = dev
        return dev
