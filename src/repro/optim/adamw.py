"""AdamW in pure JAX (pytree states, fp32 moments, bf16-safe updates)."""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class AdamWConfig:
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0


def init_moments(params) -> Dict[str, Any]:
    zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    return {"m": zeros, "v": jax.tree.map(jnp.copy, zeros),
            "step": jnp.zeros((), jnp.int32)}


def adamw_leaf(p, g, m, v, step, lr, cfg: AdamWConfig, clip_coef=1.0):
    """Single-leaf AdamW update in fp32. Returns (new_p, new_m, new_v)."""
    with jax.named_scope("adamw"):  # device-time scope, no runtime cost
        g = g.astype(jnp.float32) * clip_coef
        m = cfg.b1 * m + (1 - cfg.b1) * g
        v = cfg.b2 * v + (1 - cfg.b2) * jnp.square(g)
        t = step.astype(jnp.float32) + 1.0
        mhat = m / (1 - cfg.b1 ** t)
        vhat = v / (1 - cfg.b2 ** t)
        upd = mhat / (jnp.sqrt(vhat) + cfg.eps) + cfg.weight_decay * p.astype(jnp.float32)
        new_p = (p.astype(jnp.float32) - lr * upd).astype(p.dtype)
        return new_p, m, v


def global_norm(tree) -> jax.Array:
    return jnp.sqrt(sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
                        for l in jax.tree.leaves(tree)))


def adamw_update(params, grads, state, lr, cfg: AdamWConfig
                 ) -> Tuple[Any, Dict[str, Any]]:
    """Full-tree AdamW with global-norm clipping."""
    with jax.named_scope("adamw"):
        gnorm = global_norm(grads)
        clip = jnp.minimum(1.0, cfg.grad_clip / jnp.maximum(gnorm, 1e-9)) \
            if cfg.grad_clip > 0 else 1.0
    step = state["step"]
    out = jax.tree.map(
        lambda p, g, m, v: adamw_leaf(p, g, m, v, step, lr, cfg, clip),
        params, grads, state["m"], state["v"])
    new_p = jax.tree.map(lambda o: o[0], out, is_leaf=lambda x: isinstance(x, tuple))
    new_m = jax.tree.map(lambda o: o[1], out, is_leaf=lambda x: isinstance(x, tuple))
    new_v = jax.tree.map(lambda o: o[2], out, is_leaf=lambda x: isinstance(x, tuple))
    return new_p, {"m": new_m, "v": new_v, "step": step + 1}


# -- schedules ----------------------------------------------------------------


def cosine_schedule(base_lr: float, warmup: int, total: int, min_frac: float = 0.1):
    def lr_at(step):
        step = jnp.asarray(step, jnp.float32)
        warm = base_lr * jnp.minimum(step / max(warmup, 1), 1.0)
        prog = jnp.clip((step - warmup) / max(total - warmup, 1), 0.0, 1.0)
        cos = base_lr * (min_frac + (1 - min_frac) * 0.5 * (1 + jnp.cos(jnp.pi * prog)))
        return jnp.where(step < warmup, warm, cos)
    return lr_at
