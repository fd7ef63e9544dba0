"""WKV6 (RWKV6 "Finch" recurrence) — Pallas TPU kernel, chunked form.

The recurrence (per head, key-dim i, value-dim j):

    y_t[j]  = sum_i r_t[i] * (S_{t-1}[i,j] + u[i] k_t[i] v_t[j])
    S_t     = diag(w_t) S_{t-1} + k_t v_t^T

TPU adaptation: a sequential scan over length-``chunk`` tiles with the
(hd x hd) state held in VMEM scratch across grid steps.  Every value in the
kernel is 2-D, as Mosaic wants it:

  * the cumulative log decay ``L = cumsum(log w)`` is a lower-triangular
    (T x T) @ (T x hd) matmul;
  * the intra-chunk "attention" A[t,s] = sum_i r_t[i] k_s[i]
    exp(L_{t-1,i} - L_{s,i}) (s < t) is accumulated one key dim at a time
    as a (T x T) tile.  The exponent is formed per pair and masked before
    ``exp`` (upper-triangle exponents are positive and overflow), so strong
    decays cannot overflow the way a factorized exp(L_t) * exp(-L_s) would;
  * the inter-chunk contribution and the state update are plain
    (T x hd) @ (hd x hd) MXU matmuls.

Transposed copies (key dim on sublanes) come from an NT matmul against the
identity, which is exact at ``HIGHEST`` precision.

Grid: (B, H, n_chunks); the chunk axis is sequential ("arbitrary") so the
state scratch carries across chunks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_CHUNK = 32
_HI = lax.Precision.HIGHEST


def _mm(a, b):
    """a @ b in fp32."""
    return lax.dot_general(a, b, (((1,), (0,)), ((), ())), precision=_HI,
                           preferred_element_type=jnp.float32)


def _mm_nt(a, b):
    """a @ b.T in fp32."""
    return lax.dot_general(a, b, (((1,), (1,)), ((), ())), precision=_HI,
                           preferred_element_type=jnp.float32)


def _iota(shape, dim):
    return lax.broadcasted_iota(jnp.int32, shape, dim)


def _wkv6_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, y_ref, sT_ref,
                 s_scr, *, chunk: int):
    ic = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ic == 0)
    def _init():
        s_scr[...] = s0_ref[0, 0].astype(jnp.float32)

    T = chunk
    r = r_ref[0, 0].astype(jnp.float32)  # (T, hd)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    w = w_ref[0, 0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)  # (1, hd)
    hd = r.shape[1]
    S = s_scr[...]  # (hd, hd) state: rows = key dim, cols = value dim

    row_t, col_t = _iota((T, T), 0), _iota((T, T), 1)
    eye_hd = (_iota((hd, hd), 0) == _iota((hd, hd), 1)).astype(jnp.float32)

    # cumulative log decay L_t = sum_{s<=t} log w_s   (T, hd)
    logw = jnp.log(jnp.maximum(w, 1e-38))
    L = _mm((row_t >= col_t).astype(jnp.float32), logw)
    Lprev = L - logw  # L_{t-1}: decay applied up to t-1 *within the chunk*
    L_T = _mm_nt(eye_hd, L)  # (hd, T): L transposed
    k_T = _mm_nt(eye_hd, k)  # (hd, T)

    # inter-chunk: y_inter[t] = (r_t * exp(Lprev_t)) @ S
    y = _mm(r * jnp.exp(Lprev), S)  # (T, hd_v)

    # intra-chunk: A[t,s] = sum_i r_t[i] k_s[i] e^{Lprev_t[i] - L_s[i]} (s < t)
    lower = row_t > col_t
    lane_hd = _iota((1, hd), 1)
    sub_hd = _iota((hd, 1), 0)

    def key_dim(i, A):
        on_lane, on_sub = lane_hd == i, sub_hd == i
        r_i = jnp.sum(jnp.where(on_lane, r, 0.0), axis=1, keepdims=True)  # (T, 1)
        a_i = jnp.sum(jnp.where(on_lane, Lprev, 0.0), axis=1, keepdims=True)
        k_i = jnp.sum(jnp.where(on_sub, k_T, 0.0), axis=0, keepdims=True)  # (1, T)
        b_i = jnp.sum(jnp.where(on_sub, L_T, 0.0), axis=0, keepdims=True)
        return A + (r_i * k_i) * jnp.exp(jnp.where(lower, a_i - b_i, -jnp.inf))

    A = lax.fori_loop(0, hd, key_dim, jnp.zeros((T, T), jnp.float32))
    # diagonal bonus A[t,t] = sum_i r_t[i] u[i] k_t[i]
    diag = jnp.sum(r * u * k, axis=1, keepdims=True)  # (T, 1)
    A = A + jnp.where(row_t == col_t, diag, 0.0)
    y = y + _mm(A, v)
    y_ref[0, 0, ...] = y.astype(y_ref.dtype)

    # state update: S' = diag(e^{L_T}) S + sum_s (k_s e^{L_T - L_s}) v_s^T
    last = jnp.sum(jnp.where(_iota((1, T), 1) == T - 1, L_T, 0.0),
                   axis=1, keepdims=True)  # (hd, 1): L at the chunk's end
    S_new = jnp.exp(last) * S + _mm(k_T * jnp.exp(last - L_T), v)
    s_scr[...] = S_new

    @pl.when(ic == nc - 1)
    def _write_state():
        sT_ref[0, 0, ...] = S_new


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6_fwd(r, k, v, w, u, s0, *, chunk: int = DEFAULT_CHUNK,
             interpret: bool = False):
    """r,k,v,w: (B, H, S, hd); u: (H, hd); s0: (B, H, hd, hd).
    Returns y (B, H, S, hd) fp32, final state (B, H, hd, hd) fp32."""
    B, H, S, hd = r.shape
    chunk = min(chunk, S)
    assert S % chunk == 0
    nc = S // chunk
    grid = (B, H, nc)

    seq_spec = pl.BlockSpec((1, 1, chunk, hd), lambda b, h, c: (b, h, c, 0))
    u_spec = pl.BlockSpec((1, 1, hd), lambda b, h, c: (h, 0, 0))
    s_spec = pl.BlockSpec((1, 1, hd, hd), lambda b, h, c: (b, h, 0, 0))

    y, sT = pl.pallas_call(
        functools.partial(_wkv6_kernel, chunk=chunk),
        grid=grid,
        in_specs=[seq_spec, seq_spec, seq_spec, seq_spec, u_spec, s_spec],
        out_specs=[seq_spec, s_spec],
        out_shape=[jax.ShapeDtypeStruct((B, H, S, hd), jnp.float32),
                   jax.ShapeDtypeStruct((B, H, hd, hd), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="wkv6",
    )(r, k, v, w, u.reshape(H, 1, hd), s0)
    return y, sT
