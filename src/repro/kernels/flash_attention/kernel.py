"""Flash attention forward — Pallas TPU kernel.

TPU-native design (DESIGN.md §6): the grid is (batch, q_head, q_block,
kv_block) with the kv_block dimension iterated sequentially ("arbitrary")
so the online-softmax accumulators live in VMEM scratch across kv steps.
Q/K/V tiles are (block_q x head_dim) / (block_k x head_dim) VMEM blocks —
head_dim is kept whole (<= 256 for all assigned archs) so the MXU sees
(block_q x hd) @ (hd x block_k) matmuls with hardware-aligned contraction.

GQA is handled in the index map: kv blocks for q-head ``h`` come from kv
head ``h // group``, so K/V tiles are fetched once per group from HBM and
reused across the group's q heads via the grid order (h inner-adjacent) —
the DRAM-cache idea of the paper applied to the HBM->VMEM tier.

Causal masking skips whole (q_block, kv_block) tiles above the diagonal
(``@pl.when``), so wasted FLOPs are only the diagonal tiles' halves.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
               scale: float, causal: bool, block_q: int, block_k: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    run = True
    if causal:
        # tile is fully above the diagonal -> skip
        run = (iq + 1) * block_q > ik * block_k

    @pl.when(run)
    def _compute():
        q = q_ref[...].astype(jnp.float32)  # (block_q, hd)
        k = k_ref[...].astype(jnp.float32)  # (block_k, hd)
        v = v_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            kpos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[...] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret"))
def flash_attention_fwd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True,
                        block_q: int = DEFAULT_BLOCK_Q,
                        block_k: int = DEFAULT_BLOCK_K,
                        interpret: bool = False) -> jax.Array:
    """q: (B, H, S, hd); k, v: (B, KV, S, hd). Returns (B, H, S, hd)."""
    B, H, S, hd = q.shape
    KV = k.shape[1]
    G = H // KV
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    assert S % block_q == 0 and S % block_k == 0
    nq, nk = S // block_q, S // block_k
    scale = 1.0 / math.sqrt(hd)

    grid = (B, H, nq, nk)
    q_spec = pl.BlockSpec((1, 1, block_q, hd), lambda b, h, iq, ik: (b, h, iq, 0))
    kv_spec = pl.BlockSpec((1, 1, block_k, hd), lambda b, h, iq, ik: (b, h // G, ik, 0))
    o_spec = pl.BlockSpec((1, 1, block_q, hd), lambda b, h, iq, ik: (b, h, iq, 0))

    def kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr):
        _fa_kernel(q_ref.at[0, 0], k_ref.at[0, 0], v_ref.at[0, 0], o_ref.at[0, 0],
                   m_scr, l_scr, acc_scr, scale=scale, causal=causal,
                   block_q=block_q, block_k=block_k)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)
