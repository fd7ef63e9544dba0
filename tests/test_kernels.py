"""Per-kernel allclose vs the pure-jnp oracle, sweeping shapes/dtypes
(interpret=True executes the kernel body on CPU; every call passes it
explicitly, since the kernels compile for the TPU by default)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.kernel import flash_attention_fwd
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.mamba_scan.kernel import mamba_scan_fwd
from repro.kernels.mamba_scan.ref import mamba_scan_ref
from repro.kernels.wkv6.kernel import wkv6_fwd
from repro.kernels.wkv6.ref import wkv6_ref


@pytest.mark.parametrize("B,H,KV,S,hd,causal,dtype,tol", [
    (2, 4, 2, 256, 64, True, jnp.float32, 1e-5),
    (1, 4, 4, 128, 32, False, jnp.float32, 1e-5),
    (2, 8, 2, 256, 64, True, jnp.bfloat16, 2e-2),
    (1, 2, 1, 512, 128, True, jnp.float32, 1e-5),
    (1, 6, 2, 192, 64, True, jnp.float32, 1e-5),  # non-pow2 seq
])
def test_flash_attention(B, H, KV, S, hd, causal, dtype, tol):
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (B, H, S, hd), dtype)
    k = jax.random.normal(ks[1], (B, KV, S, hd), dtype)
    v = jax.random.normal(ks[2], (B, KV, S, hd), dtype)
    out = flash_attention_fwd(q, k, v, causal=causal, block_q=64, block_k=64,
                              interpret=True)
    exp = attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), atol=tol * 10,
                               rtol=tol * 10)


@pytest.mark.parametrize("B,H,S,hd,chunk", [
    (2, 2, 128, 16, 32),
    (1, 4, 64, 32, 16),
    (2, 2, 96, 16, 32),
    (1, 1, 64, 64, 64),
])
def test_wkv6(B, H, S, hd, chunk):
    ks = jax.random.split(jax.random.key(1), 6)
    r = jax.random.normal(ks[0], (B, H, S, hd))
    k = jax.random.normal(ks[1], (B, H, S, hd))
    v = jax.random.normal(ks[2], (B, H, S, hd))
    w = jnp.exp(-jnp.exp(jax.random.normal(ks[3], (B, H, S, hd)) * 0.5))
    u = jax.random.normal(ks[4], (H, hd)) * 0.1
    s0 = jax.random.normal(ks[5], (B, H, hd, hd)) * 0.1
    y1, st1 = wkv6_fwd(r, k, v, w, u, s0, chunk=chunk, interpret=True)
    y2, st2 = wkv6_ref(r, k, v, w, u, s0)
    # tolerance scales with output magnitude (fp32 accumulation over chunk)
    scale = float(np.max(np.abs(np.asarray(y2)))) + 1.0
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=1e-4, atol=2e-5 * scale)
    np.testing.assert_allclose(np.asarray(st1), np.asarray(st2),
                               rtol=1e-4, atol=2e-5 * scale)


@pytest.mark.parametrize("B,S,di,ds,chunk,bd", [
    (2, 64, 32, 8, 16, 16),
    (1, 128, 64, 4, 64, 32),
    (2, 32, 16, 16, 32, 16),
])
def test_mamba_scan(B, S, di, ds, chunk, bd):
    ks = jax.random.split(jax.random.key(2), 6)
    u = jax.random.normal(ks[0], (B, S, di))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, di)) - 2)
    A = -jnp.exp(jax.random.normal(ks[2], (di, ds)) * 0.3)
    Bc = jax.random.normal(ks[3], (B, S, ds))
    Cc = jax.random.normal(ks[4], (B, S, ds))
    D = jnp.ones((di,))
    h0 = jax.random.normal(ks[5], (B, di, ds)) * 0.1
    y1, h1 = mamba_scan_fwd(u, dt, A, Bc, Cc, D, h0, chunk=chunk, block_d=bd,
                            interpret=True)
    y2, h2 = mamba_scan_ref(u, dt, A, Bc, Cc, D, h0)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), rtol=1e-4, atol=1e-4)


def test_flash_attention_grad_path():
    """The custom-vjp wrapper must be differentiable (XLA ref backward)."""
    from repro.kernels.flash_attention import ops
    ks = jax.random.split(jax.random.key(4), 3)
    B, S, KV, G, hd = 1, 64, 2, 2, 16
    qg = jax.random.normal(ks[0], (B, S, KV, G, hd))
    k = jax.random.normal(ks[1], (B, S, KV, hd))
    v = jax.random.normal(ks[2], (B, S, KV, hd))
    g = jax.grad(lambda q_: ops.flash_attention(q_, k, v, causal=True,
                                               interpret=True).sum())(qg)
    assert np.isfinite(np.asarray(g)).all()


def _op_calls():
    from repro.kernels.flash_attention import ops as fa
    from repro.kernels.mamba_scan import ops as ms
    from repro.kernels.wkv6 import ops as wk
    x4 = jnp.ones((1, 32, 2, 16))
    x3 = jnp.ones((1, 32, 16))
    return {
        "flash_attention": lambda: fa.flash_attention(
            jnp.ones((1, 32, 2, 1, 16)), x4, x4),
        "wkv6": lambda: wk.wkv6(x4, x4, x4, x4 * 0.5, jnp.ones((2, 16))),
        "mamba_scan": lambda: ms.mamba_scan(x3, x3, -jnp.ones((16, 4)),
                                            jnp.ones((1, 32, 4)),
                                            jnp.ones((1, 32, 4)), jnp.ones((16,))),
    }


@pytest.mark.parametrize("name", ["flash_attention", "wkv6", "mamba_scan"])
def test_op_refuses_cpu_without_interpret(name):
    """Interpret mode is never chosen for the caller: off the TPU an op
    called without ``interpret=True`` raises instead of silently running
    the interpreter."""
    if jax.default_backend() == "tpu":
        pytest.skip("the ops compile on a TPU backend")
    with pytest.raises(ValueError, match="interpret"):
        _op_calls()[name]()
