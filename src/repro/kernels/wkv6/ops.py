"""Public wrapper for the WKV6 kernel (model layout)."""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.wkv6.kernel import wkv6_fwd


def wkv6(r, k, v, w, u, state: Optional[jax.Array] = None, *,
         interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """Model layout: r,k,v,w (B, S, H, hd); u (H, hd); state (B, H, hd, hd).
    Returns (y (B, S, H, hd) fp32, final state)."""
    B, S, H, hd = r.shape
    if state is None:
        state = jnp.zeros((B, H, hd, hd), jnp.float32)
    rt, kt, vt, wt = (jnp.moveaxis(a, 1, 2) for a in (r, k, v, w))
    y, sT = wkv6_fwd(rt, kt, vt, wt, u, state, interpret=interpret)
    return jnp.moveaxis(y, 2, 1), sT
