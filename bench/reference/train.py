"""Plain reference of the first training steps: loss, gradient, AdamW.

Follows the configuration as it is stated: the weight layout and the loss
of the reference module that its file names (``bench.spec`` loads it) in
float32 at ``Precision.HIGHEST``, global-norm clipping, AdamW with float32
moments, the learning-rate schedule of the workload file, and parameters
stored in the configuration's parameter type after every update (so a
bfloat16 configuration rounds each update to bfloat16, as training in that
type does).

It runs on one device, after the program's state is freed: parameters and
gradients live on the device in float32, and AdamW's moments on the host
between updates, one leaf on the device at a time, so that the larger
configurations fit.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from types import ModuleType
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import params as rparams


@dataclass
class Readings:
    """What one side of the comparison gives for the first ``n`` steps."""

    losses: List[float]
    #: per leaf, the first step's gradient before clipping, as the optimizer
    #: gets it (averaged over the whole global batch)
    grad_norms: Dict[str, float]
    #: per leaf, parameters after the ``n`` updates minus the initial ones
    change_norms: Dict[str, float]
    #: per leaf, the first gradient at the flat positions ``sample_index``
    grad_samples: Dict[str, np.ndarray]


#: positions of each leaf at which the first gradient is compared element
#: by element
SAMPLE = 1 << 18


def sample_index(lay) -> Dict[str, np.ndarray]:
    """The same flat positions of every leaf for both sides: all of a small
    leaf, ``SAMPLE`` drawn with a fixed generator from a large one."""
    rng = np.random.default_rng(0)
    out = {}
    for k, (shape, _) in lay.items():
        n = int(np.prod(shape))
        out[k] = np.arange(n) if n <= SAMPLE else np.sort(rng.integers(0, n, SAMPLE))
    return out


def lr_at(opt: dict, t: int) -> float:
    """Linear warm-up from 0, then cosine decay to ``min_lr_frac``."""
    lr, warm, total = opt["lr"], opt["warmup"], opt["total_steps"]
    if t < warm:
        return lr * min(t / max(warm, 1), 1.0)
    prog = min(max((t - warm) / max(total - warm, 1), 0.0), 1.0)
    f = opt["min_lr_frac"]
    return lr * (f + (1 - f) * 0.5 * (1 + math.cos(math.pi * prog)))


def _grads_fn(reference: ModuleType, cfg: dict, precision: str):
    def fn(p, tokens, labels):
        val, g = jax.value_and_grad(
            lambda q: reference.loss(cfg, precision, q, tokens, labels))(p)
        return val, g, {k: jnp.sum(jnp.square(x)) for k, x in g.items()}
    return jax.jit(fn)


@jax.jit
def _adamw_leaf(p, g, m, v, t, lr, clip, hyper):
    b1, b2, eps, wd = hyper
    g = g * clip
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    tf = t.astype(jnp.float32) + 1.0
    upd = (m / (1 - b1 ** tf)) / (jnp.sqrt(v / (1 - b2 ** tf)) + eps) + wd * p
    return p - lr * upd, m, v


def change_norms(lay, lo, hi, dtype, now: Dict[str, jax.Array]) -> Dict[str, float]:
    """Per leaf, the norm of ``now`` minus the initial weights of the seed."""
    def fn(now, lo, hi):
        p0 = rparams.init(lay, lo, hi, dtype)
        return {k: jnp.sqrt(jnp.sum(jnp.square(now[k].astype(jnp.float32)
                                               - p0[k].astype(jnp.float32))))
                for k in lay}
    out = jax.jit(fn)(now, lo, hi)
    return {k: float(x) for k, x in out.items()}


def run(reference: ModuleType, cfg: dict, opt: dict, seed: int,
        batches: Sequence[Dict[str, np.ndarray]], *, precision: str = "f32",
        rows: Optional[slice] = None, device=None) -> Readings:
    """``len(batches)`` steps from the seed's weights, with the ``layout``
    and ``loss`` of the module ``reference``.

    ``precision`` is one that the module's ``loss`` takes: "f32" is the
    reference, "fp8" the control.  ``rows`` keeps only those rows of every
    batch: the way a fault that drops part of the batch reads.
    """
    device = device or jax.devices()[0]
    lay = reference.layout(cfg)
    dtype = jnp.dtype(cfg["param_dtype"])
    lo, hi = rparams.seed_words(seed)
    hyper = tuple(jnp.float32(opt[k]) for k in ("b1", "b2", "eps", "weight_decay"))
    with jax.default_device(device):
        p = {k: x.astype(jnp.float32) for k, x in
             rparams.make_init(lay, dtype)(lo, hi).items()}
        grads_fn = _grads_fn(reference, cfg, precision)
        moments: Dict[str, tuple] = {}
        losses, first = [], None
        for t, b in enumerate(batches):
            sel = rows or slice(None)
            val, g, sq = grads_fn(p, jnp.asarray(b["tokens"][sel]),
                                  jnp.asarray(b["labels"][sel]))
            sq = {k: float(x) for k, x in sq.items()}
            losses.append(float(val))
            if first is None:
                first = {k: math.sqrt(x) for k, x in sq.items()}
                idx = sample_index(lay)
                samples = {k: np.asarray(g[k].reshape(-1)[jnp.asarray(idx[k])])
                           for k in lay}
            gnorm = math.sqrt(sum(sq.values()))
            clip = min(1.0, opt["grad_clip"] / max(gnorm, 1e-9)) \
                if opt["grad_clip"] > 0 else 1.0
            lr = lr_at(opt, t)
            for k in lay:
                if t == 0:
                    m = v = jnp.zeros(p[k].shape, jnp.float32)
                else:
                    m, v = (jax.device_put(x, device) for x in moments.pop(k))
                new, m, v = _adamw_leaf(p[k], g.pop(k), m, v, jnp.int32(t),
                                        jnp.float32(lr), jnp.float32(clip), hyper)
                p[k] = new.astype(dtype).astype(jnp.float32)
                if t + 1 < len(batches):
                    moments[k] = (np.asarray(m), np.asarray(v))
                del m, v
        return Readings(losses, first, change_norms(lay, lo, hi, dtype, p), samples)
