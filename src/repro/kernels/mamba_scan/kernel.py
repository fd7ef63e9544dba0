"""Mamba selective scan — Pallas TPU kernel.

Recurrence per channel d and state s (A diagonal):

    h_t = exp(delta_t[d] * A[d,s]) * h_{t-1} + delta_t[d] * B_t[s] * u_t[d]
    y_t[d] = sum_s C_t[s] * h_t[d,s] + D[d] * u_t[d]

TPU adaptation (DESIGN.md §6): mamba1's per-(channel,state) *diagonal*
recurrence has no matmul to feed the MXU — the natural TPU mapping is a
VPU-wide sequential loop over time with a (d_state x block_d) state slab
updated per step, channels on the lanes.  The grid is (batch, d_blocks,
time_chunks): channels are an embarrassingly parallel grid dimension (this
is where the 16384-wide d_inner of Jamba parallelizes), the time axis is
sequential with the state carried in scratch.  Chunking time bounds the
VMEM residency of the (chunk, block_d) input tiles.

The state is kept transposed, (d_state, channels), so that a time step's
channel row broadcasts over it without a relayout.  The wrapper hands the
kernel A, D and the state in that layout; B and C of a chunk are
transposed in VMEM by an exact NT matmul against the identity, and step
``t`` picks its column with a lane mask.  Per-step rows are read from
fp32 VMEM scratch with ``pl.ds``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_CHUNK = 64
DEFAULT_BLOCK_D = 256


def _mamba_kernel(u_ref, dt_ref, A_ref, B_ref, C_ref, D_ref, h0_ref,
                  y_ref, hT_ref, h_scr, dt_scr, x_scr, *, chunk: int):
    ic = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ic == 0)
    def _init():
        h_scr[...] = h0_ref[0].astype(jnp.float32)

    u = u_ref[0].astype(jnp.float32)      # (T, bd)
    dt = dt_ref[0].astype(jnp.float32)    # (T, bd)
    dt_scr[...] = dt
    x_scr[...] = dt * u
    A = A_ref[...].astype(jnp.float32)    # (ds, bd)
    ds = A.shape[0]
    eye = (lax.broadcasted_iota(jnp.int32, (ds, ds), 0)
           == lax.broadcasted_iota(jnp.int32, (ds, ds), 1)).astype(jnp.float32)
    nt = (((1,), (1,)), ((), ()))
    Bt = lax.dot_general(eye, B_ref[0].astype(jnp.float32), nt,
                         precision=lax.Precision.HIGHEST,
                         preferred_element_type=jnp.float32)  # (ds, T)
    Ct = lax.dot_general(eye, C_ref[0].astype(jnp.float32), nt,
                         precision=lax.Precision.HIGHEST,
                         preferred_element_type=jnp.float32)
    lane_t = lax.broadcasted_iota(jnp.int32, (1, chunk), 1)

    def step(t, h):
        on = lane_t == t
        b_t = jnp.sum(jnp.where(on, Bt, 0.0), axis=1, keepdims=True)  # (ds, 1)
        c_t = jnp.sum(jnp.where(on, Ct, 0.0), axis=1, keepdims=True)
        dt_t = dt_scr[pl.ds(t, 1), :]                                 # (1, bd)
        h = jnp.exp(dt_t * A) * h + b_t * x_scr[pl.ds(t, 1), :]
        y_ref[0, pl.ds(t, 1), :] = jnp.sum(c_t * h, axis=0, keepdims=True)
        return h

    h = lax.fori_loop(0, chunk, step, h_scr[...])
    y_ref[0] = y_ref[0] + u * D_ref[...].astype(jnp.float32)
    h_scr[...] = h

    @pl.when(ic == nc - 1)
    def _write_state():
        hT_ref[0, ...] = h


@functools.partial(jax.jit, static_argnames=("chunk", "block_d", "interpret"))
def mamba_scan_fwd(u, dt, A, Bc, Cc, D, h0, *, chunk: int = DEFAULT_CHUNK,
                   block_d: int = DEFAULT_BLOCK_D, interpret: bool = False):
    """u, dt: (B, S, di); A: (di, ds); Bc, Cc: (B, S, ds); D: (di,);
    h0: (B, di, ds).  Returns (y (B,S,di) fp32, hT (B,di,ds) fp32)."""
    B, S, di = u.shape
    ds = A.shape[1]
    chunk = min(chunk, S)
    block_d = min(block_d, di)
    assert S % chunk == 0 and di % block_d == 0
    nc, nd = S // chunk, di // block_d
    grid = (B, nd, nc)

    chan_spec = pl.BlockSpec((1, chunk, block_d), lambda b, d, c: (b, c, d))
    st_spec = pl.BlockSpec((1, chunk, ds), lambda b, d, c: (b, c, 0))
    A_spec = pl.BlockSpec((ds, block_d), lambda b, d, c: (0, d))
    D_spec = pl.BlockSpec((1, block_d), lambda b, d, c: (0, d))
    h_spec = pl.BlockSpec((1, ds, block_d), lambda b, d, c: (b, 0, d))

    y, hT = pl.pallas_call(
        functools.partial(_mamba_kernel, chunk=chunk),
        grid=grid,
        in_specs=[chan_spec, chan_spec, A_spec, st_spec, st_spec, D_spec, h_spec],
        out_specs=[chan_spec, h_spec],
        out_shape=[jax.ShapeDtypeStruct((B, S, di), jnp.float32),
                   jax.ShapeDtypeStruct((B, ds, di), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((ds, block_d), jnp.float32),
                        pltpu.VMEM((chunk, block_d), jnp.float32),
                        pltpu.VMEM((chunk, block_d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="mamba_scan",
    )(u, dt, A.T, Bc, Cc, D.reshape(1, di), jnp.swapaxes(h0, 1, 2))
    return y, jnp.swapaxes(hT, 1, 2)
