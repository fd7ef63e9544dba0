"""Public wrapper for the flash-attention kernel.

``flash_attention`` accepts the model's (B, S, KV, G, hd) grouped layout,
dispatches to the Pallas kernel (compiled; interpret mode only when the
caller passes ``interpret=True``), and is differentiable via a custom VJP
whose backward is the XLA reference path (forward-optimized
serving/prefill is the kernel's job; training backward stays on the XLA
path until a bwd kernel lands).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention_fwd
from repro.kernels.flash_attention.ref import attention_ref


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _fa(q, k, v, causal, interpret):
    return flash_attention_fwd(q, k, v, causal=causal, interpret=interpret)


def _fa_fwd(q, k, v, causal, interpret):
    return _fa(q, k, v, causal, interpret), (q, k, v)


def _fa_bwd(causal, interpret, res, g):
    q, k, v = res
    _, vjp = jax.vjp(lambda q_, k_, v_: attention_ref(q_, k_, v_, causal=causal),
                     q, k, v)
    return vjp(g)


_fa.defvjp(_fa_fwd, _fa_bwd)


def flash_attention(qg: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, interpret: bool = False) -> jax.Array:
    """qg: (B, S, KV, G, hd); k, v: (B, S, KV, hd) — the model layout.
    Returns (B, S, KV, G, hd)."""
    B, S, KV, G, hd = qg.shape
    q = jnp.moveaxis(qg.reshape(B, S, KV * G, hd), 1, 2)  # (B, H, S, hd)
    kk = jnp.moveaxis(k, 1, 2)  # (B, KV, S, hd)
    vv = jnp.moveaxis(v, 1, 2)
    o = _fa(q, kk, vv, causal, interpret)
    return jnp.moveaxis(o, 2, 1).reshape(B, S, KV, G, hd)
