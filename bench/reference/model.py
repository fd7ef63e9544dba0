"""Plain reference of a dense decoder's training loss (Qwen2 / Qwen3).

Written from the architectures' published equations in straightforward
``jax.numpy``; it imports nothing of the program under test.

    x   = E[tokens]
    per layer:
      h   = rmsnorm(x) * ln1
      q,k,v = h Wq (+bq), h Wk (+bk), h Wv (+bv)        (qkv bias: Qwen2)
      q,k = rmsnorm_hd(q) * q_norm, rmsnorm_hd(k) * k_norm   (qk-norm: Qwen3)
      q,k = rope(q), rope(k)                   (rotate-half, theta from cfg)
      x   = x + softmax(q k^T / sqrt(hd) + causal) v  Wo   (GQA: q head h
                                                reads kv head h // (H/KV))
      h   = rmsnorm(x) * ln2
      x   = x + (silu(h Wi) * (h Wg)) Wo_mlp
    loss = mean over tokens of logsumexp(z) - z[label],  z = rmsnorm(x) E^T

``Wi`` is the projection the activation is applied to and ``Wg`` the one
it is multiplied by, as the parameter layout (``params.py``) names them.

A precision (``PRECISIONS``) is two functions: ``mm``, through which every
matrix product goes, and ``act``, applied to the residual stream where a
layer hands it on.  The reference is float32 at ``Precision.HIGHEST`` with
the stream left alone.  The control, "fp8", is the precision one step
below the bfloat16 the configurations state: float8 (e4m3, one scale per
tensor) on every matrix operand and on the stream between layers, where
the program rounds both to bfloat16.  Attention runs in blocks of query
rows and each layer under ``jax.checkpoint``, so that the reference fits
beside nothing else on one chip at the timed sizes; blocks change memory,
not the result.
"""
from __future__ import annotations

from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

Matmul = Callable[..., jax.Array]

#: query rows per attention block and tokens per loss block
Q_BLOCK = 512
LOSS_BLOCK = 512


def mm_f32(spec: str, a, b) -> jax.Array:
    return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=lax.Precision.HIGHEST)


def _fp8(x: jax.Array) -> jax.Array:
    """Round to float8 e4m3 with one scale per tensor (amax to 448).  The
    backward pass sees the rounding as the identity, as float8 training
    keeps its gradients wider."""
    x = x.astype(jnp.float32)
    scale = 448.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    q = (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale
    return x + lax.stop_gradient(q - x)


def mm_fp8(spec: str, a, b) -> jax.Array:
    # float8 values are exact in bfloat16, and bfloat16 products are exact
    # in the float32 accumulator
    return jnp.einsum(spec, _fp8(a).astype(jnp.bfloat16),
                      _fp8(b).astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


def keep(x):
    return x


#: name -> (matrix product, rounding of the residual stream)
PRECISIONS: Dict[str, Tuple[Matmul, Callable]] = {
    "f32": (mm_f32, keep), "fp8": (mm_fp8, _fp8)}


def rmsnorm(x, scale, eps):
    x = x.astype(jnp.float32)
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def rope(x, theta):
    """x: (B, S, H, hd); rotate-half convention."""
    S, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, mm: Matmul):
    """Causal GQA attention; q: (B, S, H, hd), k/v: (B, S, KV, hd)."""
    B, S, H, hd = q.shape
    G = H // k.shape[2]
    k, v = jnp.repeat(k, G, axis=2), jnp.repeat(v, G, axis=2)
    nb = max(S // Q_BLOCK, 1)
    qb = q.reshape(B, nb, S // nb, H, hd).swapaxes(0, 1)

    @jax.checkpoint
    def block(i, qi):
        s = mm("bqhd,bkhd->bhqk", qi, k) / jnp.sqrt(jnp.float32(hd))
        rows = i * qi.shape[1] + jnp.arange(qi.shape[1])
        s = jnp.where(rows[:, None] >= jnp.arange(S)[None, :], s, -jnp.inf)
        return mm("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)

    out = lax.map(lambda a: block(*a), (jnp.arange(nb), qb))
    return out.swapaxes(0, 1).reshape(B, S, H, hd)


def layer(cfg: dict, mm: Matmul, x, p: Dict[str, jax.Array]):
    eps = cfg["rms_norm_eps"]
    h = rmsnorm(x, p["ln1/scale"], eps)
    q = mm("bsd,dhk->bshk", h, p["attn/wq"])
    k = mm("bsd,dhk->bshk", h, p["attn/wk"])
    v = mm("bsd,dhk->bshk", h, p["attn/wv"])
    if cfg["attention_bias"]:
        q = q + p["attn/bq"].astype(jnp.float32)
        k = k + p["attn/bk"].astype(jnp.float32)
        v = v + p["attn/bv"].astype(jnp.float32)
    if cfg["qk_norm"]:
        q = rmsnorm(q, p["attn/q_norm"], eps)
        k = rmsnorm(k, p["attn/k_norm"], eps)
    q, k = rope(q, cfg["rope_theta"]), rope(k, cfg["rope_theta"])
    x = x + mm("bshk,hkd->bsd", attention(q, k, v, mm), p["attn/wo"])
    h = rmsnorm(x, p["ln2/scale"], eps)
    a = jax.nn.silu(mm("bsd,df->bsf", h, p["mlp/wi"])) \
        * mm("bsd,df->bsf", h, p["mlp/wg"])
    return x + mm("bsf,fd->bsd", a, p["mlp/wo"])


def loss(cfg: dict, precision: str, params: Dict[str, jax.Array], tokens, labels):
    """Mean next-token cross entropy over every position of the batch."""
    mm, act = PRECISIONS[precision]
    x = act(params["embed"][tokens].astype(jnp.float32))
    blocks = {k[len("blocks/l0/"):]: v for k, v in params.items()
              if k.startswith("blocks/l0/")}
    x, _ = lax.scan(jax.checkpoint(lambda c, p: (act(layer(cfg, mm, c, p)), None)),
                    x, blocks)
    x = rmsnorm(x, params["final_norm/scale"], cfg["rms_norm_eps"])
    head = params["lm_head"] if "lm_head" in params else params["embed"].T
    B, S, d = x.shape
    blk = min(LOSS_BLOCK, B * S)
    xs = x.reshape(B * S // blk, blk, d)
    ys = labels.reshape(B * S // blk, blk)

    @jax.checkpoint
    def block(xy):
        z = mm("td,dv->tv", xy[0], head)
        gold = jnp.take_along_axis(z, xy[1][:, None], axis=-1)[:, 0]
        return jnp.sum(jax.nn.logsumexp(z, axis=-1) - gold)

    return jnp.sum(lax.map(block, (xs, ys))) / (B * S)
