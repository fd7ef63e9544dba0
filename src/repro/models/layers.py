"""Model primitives: norms, RoPE, attention, MLP, MoE — pure JAX.

Parameters are nested dicts of jnp arrays.  Every layer has an
``init_*(key, ...) -> params`` and an ``apply`` function.  Attention is
GQA-aware and has three implementations:

  * ``masked``  — chunked flash attention with a hand-written backward
                  (``_flash``): causal self-attention visits only the
                  tiles on or below the diagonal, and only those on it
                  build a mask,
  * ``tri``     — static triangular decomposition (recursive halving with
                  online-softmax merge; rectangles carry zero wasted FLOPs)
                  — the beyond-paper optimization logged in EXPERIMENTS §Perf,
  * ``pallas``  — the flash-attention kernel (compiled for the TPU only).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ArchConfig

Params = Dict[str, Any]

# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------


def dense_init(key, shape, in_dim, dtype):
    scale = 1.0 / math.sqrt(in_dim)
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def embed_init(key, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------


def init_norm(arch: ArchConfig, dim: int, dtype) -> Params:
    p = {"scale": jnp.ones((dim,), dtype)}
    if arch.norm == "layernorm":
        p["bias"] = jnp.zeros((dim,), dtype)
    return p


def apply_norm(arch: ArchConfig, p: Params, x: jax.Array) -> jax.Array:
    xf = x.astype(jnp.float32)
    if arch.norm == "rmsnorm":
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * lax.rsqrt(var + arch.norm_eps)
        return (y * p["scale"].astype(jnp.float32)).astype(x.dtype)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
    y = (xf - mean) * lax.rsqrt(var + arch.norm_eps)
    y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


def rms_head_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    """qk-norm: rmsnorm over head_dim."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * lax.rsqrt(var + eps) * scale.astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary / positional embeddings
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, hd); positions: (..., S) int32."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)  # (hd/2,)
    ang = positions[..., :, None].astype(jnp.float32) * freqs[None, :]  # (..., S, hd/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    cos = cos[..., :, None, :]  # broadcast over heads
    sin = sin[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(n: int, d: int) -> jax.Array:
    pos = jnp.arange(n, dtype=jnp.float32)[:, None]
    div = jnp.exp(jnp.arange(0, d, 2, dtype=jnp.float32) * (-math.log(10000.0) / d))
    pe = jnp.zeros((n, d), jnp.float32)
    pe = pe.at[:, 0::2].set(jnp.sin(pos * div))
    pe = pe.at[:, 1::2].set(jnp.cos(pos * div))
    return pe


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------


def init_attention(arch: ArchConfig, key, dtype) -> Params:
    d, H, KV, hd = arch.d_model, arch.n_heads, arch.n_kv_heads, arch.resolved_head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, H, hd), d, dtype),
        "wk": dense_init(ks[1], (d, KV, hd), d, dtype),
        "wv": dense_init(ks[2], (d, KV, hd), d, dtype),
        "wo": dense_init(ks[3], (H, hd, d), H * hd, dtype),
    }
    if arch.qkv_bias:
        p["bq"] = jnp.zeros((H, hd), dtype)
        p["bk"] = jnp.zeros((KV, hd), dtype)
        p["bv"] = jnp.zeros((KV, hd), dtype)
    if arch.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    return p


def _merge_softmax(m1, l1, o1, m2, l2, o2):
    """Merge two online-softmax partials (m: max, l: sumexp, o: weighted sum)."""
    m = jnp.maximum(m1, m2)
    a1 = jnp.exp(m1 - m)
    a2 = jnp.exp(m2 - m)
    return m, l1 * a1 + l2 * a2, o1 * a1[..., None] + o2 * a2[..., None]


def _attn_rect_chunked(q, k, v, *, q_chunk: int, kv_chunk: int, scale: float,
                       mask: Optional[str] = None, q_off: int = 0, kv_off: int = 0):
    """Rectangular attention, returns softmax partials (m, l, o).

    q: (B, Sq, KV, G, hd) grouped-query layout; k/v: (B, Sk, KV, hd).
    Memory is bounded by q_chunk x kv_chunk; FLOPs are exact (no masked
    waste unless mask='causal' is given for diagonal leaf blocks).
    Online softmax in fp32.
    """
    B, Sq, KV, G, hd = q.shape
    Sk = k.shape[1]
    q_chunk = min(q_chunk, Sq)
    kv_chunk = min(kv_chunk, Sk)
    assert Sq % q_chunk == 0 and Sk % kv_chunk == 0
    nq, nk = Sq // q_chunk, Sk // kv_chunk

    qq = q.reshape(B, nq, q_chunk, KV, G, hd)
    kk = k.reshape(B, nk, kv_chunk, KV, hd)
    vv = v.reshape(B, nk, kv_chunk, KV, hd)

    def q_block(qi, i):
        # qi: (B, q_chunk, KV, G, hd)
        def kv_step(carry, j):
            m, l, o = carry
            kj = lax.dynamic_index_in_dim(kk, j, axis=1, keepdims=False)
            vj = lax.dynamic_index_in_dim(vv, j, axis=1, keepdims=False)
            s = jnp.einsum("bqkgh,bskh->bkgqs", qi, kj,
                           preferred_element_type=jnp.float32) * scale
            if mask == "causal":
                qpos = q_off + i * q_chunk + jnp.arange(q_chunk)
                kpos = kv_off + j * kv_chunk + jnp.arange(kv_chunk)
                s = jnp.where(qpos[:, None] >= kpos[None, :], s, -jnp.inf)
            mj = jnp.max(s, axis=-1)
            mnew = jnp.maximum(m, mj)
            # guard fully-masked rows
            mnew_safe = jnp.where(jnp.isfinite(mnew), mnew, 0.0)
            p = jnp.exp(s - mnew_safe[..., None])
            p = jnp.where(jnp.isfinite(s), p, 0.0)
            alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - mnew_safe), 0.0)
            lnew = l * alpha + jnp.sum(p, axis=-1)
            onew = o * alpha[..., None] + jnp.einsum(
                "bkgqs,bskh->bkgqh", p, vj, preferred_element_type=jnp.float32)
            return (jnp.where(jnp.isfinite(mnew), mnew, -jnp.inf), lnew, onew), None

        m0 = jnp.full((B, KV, G, q_chunk), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((B, KV, G, q_chunk), jnp.float32)
        o0 = jnp.zeros((B, KV, G, q_chunk, hd), jnp.float32)
        (m, l, o), _ = lax.scan(kv_step, (m0, l0, o0), jnp.arange(nk))
        return m, l, o

    ms, ls, os_ = lax.map(lambda args: q_block(args[0], args[1]),
                          (jnp.moveaxis(qq, 1, 0), jnp.arange(nq)))
    # ms: (nq, B, KV, G, q_chunk) -> (B, KV, G, Sq)
    m = jnp.moveaxis(ms, 0, 3).reshape(B, KV, G, Sq)
    l = jnp.moveaxis(ls, 0, 3).reshape(B, KV, G, Sq)
    o = jnp.moveaxis(os_, 0, 3).reshape(B, KV, G, Sq, hd)
    return m, l, o


def _finalize(m, l, o, dtype):
    l = jnp.maximum(l, 1e-30)
    out = o / l[..., None]
    # (B, KV, G, S, hd) -> (B, S, KV, G, hd)
    return jnp.moveaxis(out, 3, 1).astype(dtype)


def _causal_tri(q, k, v, *, block: int, scale: float, q_off: int, kv_off: int,
                q_chunk: int, kv_chunk: int):
    """Static triangular decomposition of causal attention.

    Splits the sequence in halves: the second half's queries attend the
    first half's keys as a *dense rectangle* (zero masked waste), both
    halves recurse.  Leaf blocks (<= block) run dense-masked.  Total wasted
    FLOPs ~= S*block/2 instead of S^2/2.
    """
    S = q.shape[1]
    if S <= block:
        return _attn_rect_chunked(q, k, v, q_chunk=S, kv_chunk=S, scale=scale,
                                  mask="causal", q_off=q_off, kv_off=kv_off)
    h = S // 2
    q1, q2 = q[:, :h], q[:, h:]
    k1, k2 = k[:, :h], k[:, h:]
    v1, v2 = v[:, :h], v[:, h:]
    m1, l1, o1 = _causal_tri(q1, k1, v1, block=block, scale=scale,
                             q_off=q_off, kv_off=kv_off, q_chunk=q_chunk, kv_chunk=kv_chunk)
    # rectangle: q2 x (k1, v1) — no mask, exact FLOPs
    mr, lr, or_ = _attn_rect_chunked(q2, k1, v1, q_chunk=q_chunk, kv_chunk=kv_chunk,
                                     scale=scale)
    m2, l2, o2 = _causal_tri(q2, k2, v2, block=block, scale=scale,
                             q_off=q_off + h, kv_off=kv_off + h,
                             q_chunk=q_chunk, kv_chunk=kv_chunk)
    m2, l2, o2 = _merge_softmax(m2, l2, o2, mr, lr, or_)
    m = jnp.concatenate([m1, m2], axis=-1)
    l = jnp.concatenate([l1, l2], axis=-1)
    o = jnp.concatenate([o1, o2], axis=-2)
    return m, l, o


def _fit(n: int, want: int) -> int:
    """The largest chunk <= ``want`` that divides ``n``."""
    c = min(want, n)
    while c > 1 and n % c != 0:
        c -= 1
    return max(c, 1)


def _row_spans(nq: int, nk: int, q_chunk: int, kv_chunk: int, causal: bool):
    """Per q-chunk ``i``, (full, live): kv tiles ``[0, full)`` see every
    key, ``[full, live)`` need the causal mask, and tiles from ``live`` on
    are all masked and skipped.  Without the mask every tile is visited."""
    if not causal:
        return [(nk, nk)] * nq
    return [((i * q_chunk + 1) // kv_chunk, ((i + 1) * q_chunk - 1) // kv_chunk + 1)
            for i in range(nq)]


def attn_tile_counts(Sq: int, Sk: int, q_chunk: int, kv_chunk: int,
                     causal: bool) -> Tuple[int, int]:
    """(tiles visited, tiles in the grid) of ``attend(impl="masked")`` for
    the chunk sizes it is given (fitted to the lengths as ``attend`` fits
    them): causal self-attention skips the tiles above the diagonal."""
    qc, kc = _fit(Sq, q_chunk), _fit(Sk, kv_chunk)
    nq, nk = Sq // qc, Sk // kc
    spans = _row_spans(nq, nk, qc, kc, causal and Sq == Sk)
    return sum(live for _, live in spans), nq * nk


def _loop(lo: int, hi: int, body, carry):
    """``body(j, carry)`` for j in [lo, hi): a while loop, inlined when it
    runs at most once."""
    if hi - lo > 1:
        return lax.fori_loop(lo, hi, body, carry)
    for j in range(lo, hi):
        carry = body(j, carry)
    return carry


def _tile_scores(qi, kj, i, j, *, masked: bool, G: int, scale: float):
    """f32 scores of q tile ``qi`` (B, KV, G*qc, hd), rows g-major, against
    k tile ``kj`` (B, KV, kc, hd); keys after the query are -inf where
    ``masked``."""
    s = jnp.einsum("bkrh,bksh->bkrs", qi, kj,
                   preferred_element_type=jnp.float32) * scale
    if masked:
        R, kc = qi.shape[2], kj.shape[2]
        qc = R // G
        qpos = i * qc + lax.rem(lax.broadcasted_iota(jnp.int32, (R, kc), 0), qc)
        kpos = j * kc + lax.broadcasted_iota(jnp.int32, (R, kc), 1)
        s = jnp.where(qpos >= kpos, s, -jnp.inf)
    return s


def _flash_forward(qt, kt, vt, causal: bool, G: int, scale: float):
    """Online-softmax forward over the live tiles.

    qt: (nq, B, KV, G*qc, hd); kt, vt: (nk, B, KV, kc, hd); ``causal``
    masks keys after the query (self-attention, nq*qc == nk*kc).  Returns
    the normalised f32 output (nq, B, KV, G*qc, hd) and the per-row
    log-sum-exp (nq, B, KV, G*qc).  Every row sees key 0 in its first
    tile, so the running max is finite from then on and needs no guard.
    """
    nq, B, KV, R, hd = qt.shape
    nk, kc = kt.shape[0], kt.shape[3]
    os_, lses = [], []
    for i, (full, live) in enumerate(_row_spans(nq, nk, R // G, kc, causal)):
        qi = qt[i]

        def body(j, carry, masked):
            m, l, o = carry
            s = _tile_scores(qi, lax.dynamic_index_in_dim(kt, j, 0, False), i, j,
                             masked=masked, G=G, scale=scale)
            mnew = jnp.maximum(m, jnp.max(s, axis=-1))
            alpha = jnp.exp(m - mnew)
            p = jnp.exp(s - mnew[..., None])
            o = o * alpha[..., None] + jnp.einsum(
                "bkrs,bksh->bkrh", p, lax.dynamic_index_in_dim(vt, j, 0, False),
                preferred_element_type=jnp.float32)
            return mnew, l * alpha + jnp.sum(p, axis=-1), o

        carry = (jnp.full((B, KV, R), -jnp.inf, jnp.float32),
                 jnp.zeros((B, KV, R), jnp.float32),
                 jnp.zeros((B, KV, R, hd), jnp.float32))
        carry = _loop(0, full, partial(body, masked=False), carry)
        m, l, o = _loop(full, live, partial(body, masked=True), carry)
        os_.append(o / l[..., None])
        lses.append(m + jnp.log(l))
    return jnp.stack(os_), jnp.stack(lses)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(qt, kt, vt, causal: bool, G: int, scale: float):
    """Exact attention in the tiled layout of :func:`_flash_forward`,
    differentiated by the FlashAttention-2 backward: no score tile is
    kept past its iteration, and tiles above the diagonal are skipped."""
    return _flash_forward(qt, kt, vt, causal, G, scale)[0]


def _flash_fwd(qt, kt, vt, causal, G, scale):
    o, lse = _flash_forward(qt, kt, vt, causal, G, scale)
    return o, (qt, kt, vt, o, lse)


def _flash_bwd(causal, G, scale, res, do):
    """Per live tile: recompute p = exp(s - lse), then dV += pᵀdO,
    dP = dO Vᵀ, dS = p (dP - D) with D = rowsum(dO * o), dQ += dS K,
    dK += dSᵀQ.  The matmuls take the operand dtypes autodiff of the
    chunked online softmax gave them (f32 p, dO and dS; q, k, v as
    stored); every sum accumulates in f32."""
    qt, kt, vt, o, lse = res
    nq, B, KV, R, hd = qt.shape
    nk, kc = kt.shape[0], kt.shape[3]
    D = jnp.sum(do * o, axis=-1)
    dk = jnp.zeros((nk, B, KV, kc, hd), jnp.float32)
    dv = jnp.zeros((nk, B, KV, kc, hd), jnp.float32)
    dqs = []

    def add_at(acc, j, x):
        return lax.dynamic_update_index_in_dim(
            acc, lax.dynamic_index_in_dim(acc, j, 0, False) + x, j, 0)

    for i, (full, live) in enumerate(_row_spans(nq, nk, R // G, kc, causal)):
        qi, doi, lse_i, D_i = qt[i], do[i], lse[i], D[i]

        def body(j, carry, masked):
            dq, dk, dv = carry
            kj = lax.dynamic_index_in_dim(kt, j, 0, False)
            vj = lax.dynamic_index_in_dim(vt, j, 0, False)
            s = _tile_scores(qi, kj, i, j, masked=masked, G=G, scale=scale)
            p = jnp.exp(s - lse_i[..., None])
            dv = add_at(dv, j, jnp.einsum("bkrs,bkrh->bksh", p, doi,
                                          preferred_element_type=jnp.float32))
            dp = jnp.einsum("bkrh,bksh->bkrs", doi, vj,
                            preferred_element_type=jnp.float32)
            ds = p * (dp - D_i[..., None]) * scale
            dq = dq + jnp.einsum("bkrs,bksh->bkrh", ds, kj,
                                 preferred_element_type=jnp.float32)
            dk = add_at(dk, j, jnp.einsum("bkrs,bkrh->bksh", ds, qi,
                                          preferred_element_type=jnp.float32))
            return dq, dk, dv

        carry = (jnp.zeros((B, KV, R, hd), jnp.float32), dk, dv)
        carry = _loop(0, full, partial(body, masked=False), carry)
        dq, dk, dv = _loop(full, live, partial(body, masked=True), carry)
        dqs.append(dq)
    return (jnp.stack(dqs).astype(qt.dtype), dk.astype(kt.dtype),
            dv.astype(vt.dtype))


_flash.defvjp(_flash_fwd, _flash_bwd)


def attend(q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool,
           impl: str = "masked", block: int = 1024,
           q_chunk: int = 1024, kv_chunk: int = 1024,
           gqa_repeat: bool = False) -> jax.Array:
    """Multi-head attention core.

    q: (B, Sq, H, hd); k, v: (B, Sk, KV, hd); H = KV * G.
    Returns (B, Sq, H, hd).

    ``gqa_repeat``: materialize K/V per q-head (KV'=H, G'=1) instead of the
    grouped (KV, G) layout.  Under TP the grouped reshape fragments an
    H-sharded head dim into (KV, G) factors that rarely divide the TP
    degree, forcing XLA to regather Q every layer; repeating K/V keeps the
    head dim whole and every attention einsum shard-local (§Perf).
    """
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    if gqa_repeat and G > 1:
        k = jnp.repeat(k, G, axis=2)
        v = jnp.repeat(v, G, axis=2)
        KV, G = H, 1
    scale = 1.0 / math.sqrt(hd)
    qg = q.reshape(B, Sq, KV, G, hd)
    if impl == "pallas":
        from repro.kernels.flash_attention import ops as fa_ops
        out = fa_ops.flash_attention(qg, k, v, causal=causal)
        return out.reshape(B, Sq, H, hd)
    Sk = k.shape[1]
    if impl == "tri":
        if causal and Sq == Sk and Sq > block and Sq % block == 0:
            m, l, o = _causal_tri(qg, k, v, block=block, scale=scale, q_off=0,
                                  kv_off=0, q_chunk=_fit(Sq, q_chunk),
                                  kv_chunk=_fit(Sq, kv_chunk))
        else:
            mask = "causal" if (causal and Sq == Sk) else None
            m, l, o = _attn_rect_chunked(qg, k, v, q_chunk=_fit(Sq, q_chunk),
                                         kv_chunk=_fit(Sk, kv_chunk),
                                         scale=scale, mask=mask)
        return _finalize(m, l, o, q.dtype).reshape(B, Sq, H, hd)
    # the tiled layout: q rows (g, position) of each q-chunk side by side,
    # so each (chunk, kv head) is one (G*qc) x kc matmul
    qc, kc = _fit(Sq, q_chunk), _fit(Sk, kv_chunk)
    nq, nk = Sq // qc, Sk // kc
    qt = qg.reshape(B, nq, qc, KV, G, hd).transpose(1, 0, 3, 4, 2, 5)
    kt = k.reshape(B, nk, kc, KV, hd).transpose(1, 0, 3, 2, 4)
    vt = v.reshape(B, nk, kc, KV, hd).transpose(1, 0, 3, 2, 4)
    o = _flash(qt.reshape(nq, B, KV, G * qc, hd), kt, vt, causal and Sq == Sk,
               G, scale)
    o = o.reshape(nq, B, KV, G, qc, hd).transpose(1, 0, 4, 2, 3, 5)
    return o.reshape(B, Sq, H, hd).astype(q.dtype)


def attend_decode(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                  pos: jax.Array) -> jax.Array:
    """Single-token decode attention over a (B, S_max, KV, hd) cache.

    ``pos`` (B,) int32: number of valid cache entries (the new token's kv
    must already be written at pos-1... pos).  Masked softmax over S_max.
    """
    B, Sq, H, hd = q.shape
    KV = k_cache.shape[2]
    G = H // KV
    scale = 1.0 / math.sqrt(hd)
    qg = q.reshape(B, Sq, KV, G, hd)
    s = jnp.einsum("bqkgh,bskh->bkgqs", qg, k_cache,
                   preferred_element_type=jnp.float32) * scale
    S = k_cache.shape[1]
    valid = jnp.arange(S)[None, :] < pos[:, None]  # (B, S)
    s = jnp.where(valid[:, None, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskh->bkgqh", p, v_cache,
                   preferred_element_type=jnp.float32)
    return jnp.moveaxis(o, 3, 1).astype(q.dtype).reshape(B, Sq, H, hd)


def attention_qkv(arch: ArchConfig, p: Params, x: jax.Array,
                  positions: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Project to q, k, v with bias / qk-norm / rope per the arch."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if arch.qkv_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    if arch.qk_norm:
        q = rms_head_norm(q, p["q_norm"], arch.norm_eps)
        k = rms_head_norm(k, p["k_norm"], arch.norm_eps)
    if arch.positional == "rope":
        q = apply_rope(q, positions, arch.rope_theta)
        k = apply_rope(k, positions, arch.rope_theta)
    return q, k, v


def attention_out(p: Params, o: jax.Array) -> jax.Array:
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"])


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------


def _act(kind: str, x: jax.Array) -> jax.Array:
    if kind == "silu":
        return jax.nn.silu(x)
    if kind == "gelu":
        return jax.nn.gelu(x)
    if kind == "relu2":
        r = jax.nn.relu(x)
        return r * r
    raise ValueError(kind)


def init_mlp(arch: ArchConfig, key, dtype, d_ff: Optional[int] = None) -> Params:
    d, f = arch.d_model, d_ff or arch.d_ff
    ks = jax.random.split(key, 3)
    p = {"wi": dense_init(ks[0], (d, f), d, dtype),
         "wo": dense_init(ks[1], (f, d), f, dtype)}
    if arch.glu:
        p["wg"] = dense_init(ks[2], (d, f), d, dtype)
    return p


def apply_mlp(arch: ArchConfig, p: Params, x: jax.Array) -> jax.Array:
    h = _act(arch.activation, x @ p["wi"])
    if arch.glu:
        h = h * (x @ p["wg"])
    return h @ p["wo"]


# ---------------------------------------------------------------------------
# MoE (capacity-based gather/scatter dispatch, EP over the model axis)
# ---------------------------------------------------------------------------


def moe_capacity(tokens: int, top_k: int, num_experts: int,
                 capacity_factor: float) -> int:
    """Per-group expert capacity ``C`` — THE formula the dispatch pads
    to, shared with the dispatch planner (``moe_dispatch_schedule``) so
    the planned per-expert flow sizes are exactly what ``_moe_dispatch``
    moves."""
    C = int(max(8, math.ceil(tokens * top_k / num_experts
                             * capacity_factor)))
    return min(C, tokens)


def moe_expert_capacities(counts, tokens: int,
                          capacity_factor: float) -> Tuple[int, ...]:
    """Per-expert capacity twin of :func:`moe_capacity` — size expert
    ``e``'s slab from its MEASURED routed-token count instead of the
    uniform ``tokens * top_k / num_experts`` prior.  Under uniform
    counts (``cnt_e == tokens * top_k / E``) this reduces to exactly
    ``moe_capacity`` for every expert, so skew-aware planning is a
    strict generalization, not a fork of the formula."""
    return tuple(min(int(max(8, math.ceil(float(c) * capacity_factor))),
                     tokens) for c in counts)


def moe_dispatch_schedule(arch: ArchConfig, tokens_per_member: int,
                          planner, groups: int = 1,
                          router_logits=None):
    """Planner-searched all-to-all schedule for the MoE dispatch — the
    §Perf cell C traffic as per-expert NIC-pool / memory-pool flows.

    The dispatch buffer is ``(G, E, C, d)`` with ``C`` from
    :func:`moe_capacity`; with the experts spread over the ``n`` members
    of the planner's DP domain (expert parallelism), member *r* owns
    ``E // n`` expert slabs and every member sends it ``C * d`` elements
    per owned expert per group — so row *r* of the exchange payload is
    ``groups * (E // n) * C * d`` elements and the slow-tier sub-flows
    the simulator replays are exactly the per-expert (per-destination)
    flows.  ``planner`` is a :class:`repro.core.planner.Planner`; the
    result is a ``kind="all_to_all"`` :class:`CommSchedule` with the
    chunk count and staging placement searched per
    ``Planner.plan_all_to_all``, and ``apply_moe(dispatch_schedule=...)``
    guards against capacity drift.

    ``router_logits`` (optional, shape ``(tokens_per_member, E)`` or
    ``(G, tokens_per_group, E)``): MEASURED router logits from a
    profiling step.  When given, each expert's slab is sized from its
    own routed-token count (:func:`moe_expert_capacities`, max over
    groups), the dispatch buffer pads to ``C_exec = max_e C_e``, and
    the schedule carries per-MEMBER ``dest_sizes`` — member *r*
    receives ``G * sum(C_e for e in r's slab) * d`` elements, so hot
    experts become hot per-destination flows the cost model's incast
    bound, the simulator and the planner's path split all see.  Cold
    experts' padding (``C_exec - C_e``) stays off the wire.  ``None``
    keeps the uniform-prior path bit-for-bit."""
    moe = arch.moe
    G = max(groups, 1)
    tokens_per_group = tokens_per_member // G
    n = planner.domain_size  # the domain the planner actually plans for
    if n > 1 and moe.num_experts % n != 0:
        # a floored E//n would silently drop part of the dispatch
        # traffic from the plan (and the drift guard, built from the
        # same division, could never catch it)
        raise ValueError(
            f"num_experts={moe.num_experts} does not divide over the "
            f"{n}-member DP domain — expert parallelism needs "
            f"E % members == 0 to plan per-expert flows")
    experts_per_member = max(moe.num_experts // max(n, 1), 1)
    if router_logits is None:
        C = moe_capacity(tokens_per_group, moe.top_k, moe.num_experts,
                         moe.capacity_factor)
        shape = (n, G * experts_per_member * C * arch.d_model)
        return planner.plan_all_to_all(shape)
    import numpy as np
    lg = np.asarray(router_logits, dtype=np.float32)
    if lg.ndim == 2:
        lg = lg.reshape(G, tokens_per_group, -1)
    if lg.shape != (G, tokens_per_group, moe.num_experts):
        raise ValueError(
            f"router_logits shape {np.asarray(router_logits).shape} does "
            f"not cover ({tokens_per_member}, {moe.num_experts}) tokens x "
            f"experts in {G} group(s)")
    # per-group top-k routing counts (top-k of logits == top-k of the
    # softmax'd probs the layer routes on — softmax is monotonic)
    k = moe.top_k
    top = np.argpartition(-lg, k - 1, axis=-1)[..., :k]  # (G, Tl, k)
    caps = np.zeros(moe.num_experts, dtype=np.int64)
    for g in range(G):
        cnt = np.bincount(top[g].ravel(), minlength=moe.num_experts)
        caps = np.maximum(caps, moe_expert_capacities(
            cnt, tokens_per_group, moe.capacity_factor))
    c_exec = int(caps.max())
    shape = (n, G * experts_per_member * c_exec * arch.d_model)
    from repro.core.cost_model import dtype_itemsize
    esz = dtype_itemsize("float32")
    dest_sizes = [
        float(G * int(caps[r * experts_per_member:
                           (r + 1) * experts_per_member].sum())
              * arch.d_model * esz)
        for r in range(n)]
    return planner.plan_all_to_all(shape, dest_sizes=dest_sizes)


def init_moe(arch: ArchConfig, key, dtype) -> Params:
    moe = arch.moe
    d, f, E = arch.d_model, moe.expert_d_ff, moe.num_experts
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], (d, E), d, jnp.float32),
        "we_in": dense_init(ks[1], (E, d, f), d, dtype),
        "we_out": dense_init(ks[2], (E, f, d), f, dtype),
    }
    if arch.glu:
        p["we_gate"] = dense_init(ks[3], (E, d, f), d, dtype)
    if moe.num_shared_experts:
        shared = arch.replace(d_ff=f * moe.num_shared_experts)
        p["shared"] = init_mlp(shared, ks[4], dtype, d_ff=f * moe.num_shared_experts)
    return p


def apply_moe(arch: ArchConfig, p: Params, x: jax.Array, groups: int = 1,
              dispatch_spec=None,
              dispatch_schedule=None) -> Tuple[jax.Array, jax.Array]:
    """Returns (output, aux_load_balance_loss). x: (B, S, d).

    ``groups`` > 1 splits the tokens into independent dispatch groups
    (routing/cumsum/capacity per group).  With groups == the DP degree and
    the group dim sharded over DP, the dispatch gather/scatter stays inside
    each DP shard — no cross-pod incast from global-cumsum dependencies
    (§Perf, the MoE NIC-pool fix).  ``dispatch_spec``: optional
    (dp_spec_entry, tp_axis) used to pin the dispatched (G, E, C, d)
    buffers to group-x-expert sharding.

    ``dispatch_schedule``: the planner-searched ``kind="all_to_all"``
    :class:`~repro.core.schedule.CommSchedule` for this layer's dispatch
    (:func:`moe_dispatch_schedule` — per-expert flow sizes from the
    capacity ``C``), the cell C plan the cost model prices and
    ``repro.sim.fabric_sim`` replays through the NIC/memory pools.  The
    schedule is EXECUTED: the dispatch buffer is routed through the
    plan's slow-leg chunk split / issue order / reassembly
    (:func:`_execute_dispatch` inside :func:`_moe_dispatch`), so the
    numbers the plan is priced at are the numbers the layer runs —
    bitwise-identical to the unscheduled dispatch because the walk is a
    pure slice/concat identity.  A skew-planned schedule (per-member
    ``dest_sizes`` from measured router logits) also carries the
    per-expert capacity: the layer pads to the schedule's
    ``C_exec = max_e C_e`` instead of the uniform prior.  A schedule
    whose payload does not match the dispatch buffer actually built
    (capacity drift — tokens, top-k or capacity_factor changed after
    planning) is rejected loudly instead of silently mispricing cell
    C."""
    moe = arch.moe
    B, S, d = x.shape
    T = B * S
    xt = x.reshape(T, d)
    G = groups if (groups > 1 and T % groups == 0) else 1
    sched_capacity = None
    if dispatch_schedule is not None:
        if dispatch_schedule.kind != "all_to_all":
            raise ValueError(
                f"dispatch_schedule must be an all_to_all schedule, got "
                f"kind={dispatch_schedule.kind!r}")
        n = int(dispatch_schedule.shape[0])
        if n > 1 and moe.num_experts % n != 0:
            raise ValueError(
                f"num_experts={moe.num_experts} does not divide over the "
                f"schedule's {n}-member domain — per-expert flows need "
                f"E % members == 0")
        epm = max(moe.num_experts // max(n, 1), 1)
        skewed = any(getattr(l, "dest_sizes", None) is not None
                     for l in dispatch_schedule.legs)
        if skewed:
            # skew-planned: the schedule OWNS the capacity (C_exec =
            # max_e C_e from measured routing) — recover it from the
            # payload and dispatch at it
            denom = n * G * epm * d
            c_exec = dispatch_schedule.numel // denom
            if c_exec < 1 or c_exec * denom != dispatch_schedule.numel:
                raise ValueError(
                    f"dispatch_schedule planned for a different dispatch "
                    f"buffer: schedule carries {dispatch_schedule.numel} "
                    f"elements, not divisible into (G={G}, "
                    f"E={moe.num_experts}, d={d}, members={n}) expert "
                    f"slabs — rebuild with moe_dispatch_schedule()")
            sched_capacity = int(c_exec)
        else:
            C = moe_capacity(T // G, moe.top_k, moe.num_experts,
                             moe.capacity_factor)
            want = n * G * epm * C * d
            if dispatch_schedule.numel != want:
                raise ValueError(
                    f"dispatch_schedule planned for a different dispatch "
                    f"buffer: schedule carries {dispatch_schedule.numel} "
                    f"elements, this layer dispatches {want} "
                    f"(G={G}, E={moe.num_experts}, C={C}, d={d}, "
                    f"members={n}) — rebuild with moe_dispatch_schedule()")
    # NOTE (§Perf): the vmapped per-group dispatch partitions better than
    # both a flat group-global gather and explicitly-constrained dispatch
    # buffers (2.5x vs 0.4x / 0.65x on deepseek prefill_32k) — XLA keeps
    # vmapped gathers group-local.
    if G > 1:
        yg, auxg = jax.vmap(
            lambda xx: _moe_dispatch(arch, p, xx[None],
                                     capacity=sched_capacity,
                                     dispatch_schedule=dispatch_schedule)
        )(xt.reshape(G, T // G, d))
        y, aux = yg.reshape(T, d), jnp.mean(auxg)
    else:
        y1, aux = _moe_dispatch(arch, p, xt[None], capacity=sched_capacity,
                                dispatch_schedule=dispatch_schedule)
        y = y1.reshape(T, d)
    if moe.num_shared_experts:
        shared = arch.replace(d_ff=moe.expert_d_ff * moe.num_shared_experts)
        y = y + apply_mlp(shared, p["shared"], xt)
    return y.reshape(B, S, d), aux


def _execute_dispatch(schedule, xe: jax.Array) -> jax.Array:
    """Run the (G, E, C, d) dispatch buffer through ``schedule``'s
    slow-leg walk — the member-major view split at the plan's chunk
    boundaries, sub-flows taken in the plan's ISSUE order (lane-offset
    rotation included, since ``with_lane_offset`` reorders the legs),
    then reassembled by chunk index, exactly like
    ``collectives.lower_all_to_all``'s slow stage.  The walk is a pure
    slice/concat identity (the member exchange itself is the rectangular
    capacity-padded payload), so the output is bitwise ``xe`` — but the
    plan's chunking now IS the executed dataflow, not an annotation.

    Chunk bounds are proportional (``(j * cols) // chunks``) rather than
    ``cols // chunks`` blocks so a per-group buffer that does not divide
    evenly still reassembles exactly."""
    G, E, C, d = xe.shape
    n = int(schedule.shape[0])
    slow = schedule.slow_legs
    if n <= 1 or E % n != 0 or not slow:
        return xe
    # member-major rows: member r's slab = experts [r*epm, (r+1)*epm)
    buf = jnp.transpose(xe, (1, 0, 2, 3)).reshape(n, -1)
    cols = buf.shape[1]
    k = len(slow)
    bounds = [(j * cols) // k for j in range(k + 1)]
    outs: list = [None] * k
    for leg in slow:  # issue order; payload slice picked by index
        j = leg.index
        outs[j] = lax.slice_in_dim(buf, bounds[j], bounds[j + 1], axis=1)
    buf = jnp.concatenate(outs, axis=1) if k > 1 else outs[0]
    return jnp.transpose(buf.reshape(n, E // n, G, C, d),
                         (2, 0, 1, 3, 4)).reshape(G, E, C, d)


def _moe_dispatch(arch: ArchConfig, p: Params, xg: jax.Array,
                  dispatch_spec=None, capacity: Optional[int] = None,
                  dispatch_schedule=None) -> Tuple[jax.Array, jax.Array]:
    """Capacity-based top-k dispatch on grouped (G, Tl, d) token slabs.

    All routing math is per-group (cumsum over the group's own tokens), so
    a group never depends on another group's tokens; gathers/scatters use
    group-global flat indices so the whole pipeline keeps the group dim
    sharded over DP and the expert dim sharded over TP.

    ``capacity`` overrides the uniform-prior :func:`moe_capacity` with a
    planned per-expert ``C_exec`` (skew-aware scheduling);
    ``dispatch_schedule`` routes the dispatch buffer through the
    planned chunk walk (:func:`_execute_dispatch`)."""
    moe = arch.moe
    G, Tl, d = xg.shape
    E, k = moe.num_experts, moe.top_k

    logits = (xg.astype(jnp.float32) @ p["router"])  # (G, Tl, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, topk_idx = lax.top_k(probs, k)  # (G, Tl, k)
    gate_vals = gate_vals / jnp.maximum(jnp.sum(gate_vals, -1, keepdims=True), 1e-9)

    # load-balance aux loss (Switch-style, averaged over groups)
    me = jnp.mean(probs, axis=(0, 1))  # (E,)
    ce = jnp.mean(jax.nn.one_hot(topk_idx[..., 0], E, dtype=jnp.float32), axis=(0, 1))
    aux = E * jnp.sum(me * ce)

    # capacity per group (the shared formula the dispatch planner sizes
    # per-expert flows from); a skew-planned schedule overrides it with
    # its own C_exec = max_e C_e
    C = capacity if capacity is not None \
        else moe_capacity(Tl, k, E, moe.capacity_factor)

    flat_e = topk_idx.reshape(G, Tl * k)
    flat_g = gate_vals.reshape(G, Tl * k)
    tok_id = jnp.broadcast_to(jnp.repeat(jnp.arange(Tl), k)[None], (G, Tl * k))

    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)  # (G, Tl*k, E)
    pos_in_e = (jnp.cumsum(onehot, axis=1) - 1)
    pos = jnp.sum(pos_in_e * onehot, axis=-1)  # (G, Tl*k)

    # scatter per-group token ids into (G, E, C); overflow (pos >= C) drops
    g_ix = jnp.broadcast_to(jnp.arange(G)[:, None], (G, Tl * k))
    dis = jnp.full((G, E, C), Tl, jnp.int32)
    dis = dis.at[g_ix, flat_e, pos].set(tok_id, mode="drop")
    gat = jnp.zeros((G, E, C), jnp.float32)
    gat = gat.at[g_ix, flat_e, pos].set(flat_g, mode="drop")

    # group-global flat gather: device (g-shard, e-shard) reads only its
    # own group's tokens
    x_pad = jnp.concatenate([xg, jnp.zeros((G, 1, d), xg.dtype)], axis=1)
    xf = x_pad.reshape(G * (Tl + 1), d)
    gidx = dis + (jnp.arange(G) * (Tl + 1))[:, None, None]
    xe = xf[gidx]  # (G, E, C, d)
    if dispatch_schedule is not None:
        # execute the planned dispatch: the buffer rides the schedule's
        # chunk split / issue order / reassembly (bitwise identity)
        xe = _execute_dispatch(dispatch_schedule, xe)
    if dispatch_spec is not None:
        from jax.sharding import PartitionSpec as P
        dp, tp = dispatch_spec
        xe = lax.with_sharding_constraint(xe, P(dp, tp, None, None))

    h = jnp.einsum("gecd,edf->gecf", xe, p["we_in"])
    h = _act(arch.activation, h)
    if arch.glu:
        h = h * jnp.einsum("gecd,edf->gecf", xe, p["we_gate"])
    ye = jnp.einsum("gecf,efd->gecd", h, p["we_out"])  # (G, E, C, d)

    ye = ye * gat[..., None].astype(ye.dtype)
    if dispatch_spec is not None:
        from jax.sharding import PartitionSpec as P
        dp, tp = dispatch_spec
        ye = lax.with_sharding_constraint(ye, P(dp, tp, None, None))
    y = jnp.zeros((G * (Tl + 1), d), ye.dtype).at[gidx.reshape(-1)].add(
        ye.reshape(-1, d), mode="drop")
    y = y.reshape(G, Tl + 1, d)[:, :Tl]
    return y, aux
