"""Coverage for the JAX-side memory-pool analogues (repro.core.staging_utils,
formerly repro.core.memory_pool — the old path survives as a deprecation shim).

These utilities map the paper's §4.1/§4.3 mechanisms onto TPU-native
idioms; until now they shipped untested:

  * :func:`donated_jit` — pass-by-reference: the carry buffers of step t
    must actually be REUSED by step t+1 (input invalidated, output
    aliased onto the donated allocation), not copied;
  * :class:`StagingBuffers` — the virt_queue RX analogue must round-robin
    its slots and preserve the target sharding;
  * :func:`offload_sharding` — host-DRAM offload must fall back cleanly
    on backends without ``pinned_host`` (the CPU backend here).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P


def test_donated_jit_reuses_buffers_across_steps():
    from repro.core.staging_utils import donated_jit

    @donated_jit
    def step(params, opt, grads):
        return params - 0.1 * grads, opt + 1.0

    p = jnp.ones((4096,))
    o = jnp.zeros((4096,))
    g = jnp.full((4096,), 0.5)
    p_ptr = p.unsafe_buffer_pointer()
    o_ptr = o.unsafe_buffer_pointer()
    p2, o2 = step(p, o, g)
    # donated carries are invalidated; the non-donated operand survives
    assert p.is_deleted() and o.is_deleted()
    assert not g.is_deleted()
    # ... and the outputs live in the donated allocations (true aliasing,
    # not just invalidation): step t+1 consumes step t's buffers in place
    assert {p2.unsafe_buffer_pointer(), o2.unsafe_buffer_pointer()} \
        == {p_ptr, o_ptr}
    np.testing.assert_allclose(np.asarray(p2), 1.0 - 0.05)
    # the chain keeps donating across steps
    p3, o3 = step(p2, o2, jnp.zeros((4096,)))
    assert p2.is_deleted() and o2.is_deleted()
    np.testing.assert_allclose(np.asarray(o3), 2.0)


def test_donated_jit_custom_argnums():
    from repro.core.staging_utils import donated_jit

    @donated_jit(donate_argnums=(1,))
    def f(x, carry):
        return x + carry

    x = jnp.ones((16,))
    c = jnp.ones((16,))
    f(x, c)
    assert not x.is_deleted()
    assert c.is_deleted()


def test_staging_buffers_round_robin_and_sharding():
    from repro.core.staging_utils import StagingBuffers
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
    sharding = NamedSharding(mesh, P())
    staging = StagingBuffers(sharding, n_slots=2)
    batches = [np.full((8,), float(i), np.float32) for i in range(4)]
    outs = [staging.put(b) for b in batches]
    for i, out in enumerate(outs):
        assert out.sharding.is_equivalent_to(sharding, out.ndim)
        np.testing.assert_array_equal(np.asarray(out), batches[i])
    # slots round-robin: batch i lands in slot i % 2, and the slot holds
    # the LAST batch written to it
    assert staging._slots[0] is outs[2]
    assert staging._slots[1] is outs[3]
    assert staging._next == 0  # wrapped around


def test_offload_sharding_falls_back_without_pinned_host():
    from repro.core.staging_utils import (host_memory_kind_available,
                                        offload_sharding)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
    plain = offload_sharding(mesh, P(), offload=False)
    assert isinstance(plain, NamedSharding)
    offloaded = offload_sharding(mesh, P(), offload=True)
    # on a backend without the pinned_host memory kind the offload
    # request must degrade to the plain device sharding, not raise
    if not host_memory_kind_available():
        assert offloaded.memory_kind == plain.memory_kind
    # either way the result must be usable for an actual placement
    x = jax.device_put(np.ones((4,), np.float32), offloaded)
    np.testing.assert_array_equal(np.asarray(x), 1.0)


def test_memory_pool_shim_reexports_with_deprecation():
    # the pre-rename import path must keep working (one release of grace)
    # but warn: repro.core.memory_pool collided with repro.core.mempool
    import importlib
    import sys
    import warnings
    sys.modules.pop("repro.core.memory_pool", None)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        shim = importlib.import_module("repro.core.memory_pool")
    assert any(issubclass(x.category, DeprecationWarning) for x in w)
    from repro.core import staging_utils
    for name in ("donated_jit", "host_memory_kind_available",
                 "with_memory_kind", "offload_sharding", "StagingBuffers"):
        assert getattr(shim, name) is getattr(staging_utils, name)
