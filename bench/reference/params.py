"""Weights of a benchmark configuration, made from the run's seed.

The benchmark makes the weights itself, so that the plain reference never
takes weights that the program under test made.  A layout is the
interface the program's training step takes: one flat path per leaf
(``blocks/l0/attn/wq``), each block leaf stacked over the layers on its
first axis.  Each configuration's reference module gives its own
(``layout(cfg)``); what is here serves every layout.  Both sides call
:func:`init` with the same seed and get the same numbers: on the device,
in one jitted call, in the configuration's parameter type.

Distributions: matrices are normal with standard deviation
``1/sqrt(fan_in)``, the embedding table normal with 0.02, attention biases
normal with 0.02 (so that the bias takes part in the first forward pass),
and every norm scale is 1.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

#: path -> (shape, initialiser); an initialiser is "ones", "embed", "bias"
#: or the fan-in of a matrix as a decimal string
Layout = Dict[str, Tuple[Tuple[int, ...], str]]


def seed_words(seed: int) -> Tuple[jax.Array, jax.Array]:
    """A seed of any size as two uint32 words (low, high)."""
    if seed < 0 or seed >= 2 ** 64:
        raise ValueError(f"seed {seed} is outside [0, 2**64)")
    return jnp.uint32(seed & 0xFFFFFFFF), jnp.uint32(seed >> 32)


def make_init(lay: Layout, dtype, out_shardings=None):
    """One jitted call ``(lo, hi) -> {path: leaf}``: the seed words are
    arguments, so one compiled program serves every seed."""
    return jax.jit(lambda lo, hi: init(lay, lo, hi, dtype),
                   out_shardings=out_shardings)


def init(lay: Layout, lo: jax.Array, hi: jax.Array, dtype) -> Dict[str, jax.Array]:
    """Every leaf of ``lay`` from the seed words."""
    key = jax.random.fold_in(jax.random.key(lo), hi)
    out = {}
    for i, (path, (shape, kind)) in enumerate(lay.items()):
        if kind == "ones":
            out[path] = jnp.ones(shape, dtype)
            continue
        std = {"embed": 0.02, "bias": 0.02}.get(kind)
        if std is None:
            std = float(kind) ** -0.5
        x = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        out[path] = (x * std).astype(dtype)
    return out
