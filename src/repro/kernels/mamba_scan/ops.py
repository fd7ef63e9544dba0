"""Public wrapper for the mamba selective-scan kernel."""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.mamba_scan.kernel import mamba_scan_fwd


def mamba_scan(u, dt, A, Bc, Cc, D, state: Optional[jax.Array] = None, *,
               interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """Model layout (same as kernel). state defaults to zeros."""
    B, S, di = u.shape
    ds = A.shape[1]
    if state is None:
        state = jnp.zeros((B, di, ds), jnp.float32)
    return mamba_scan_fwd(u, dt, A, Bc, Cc, D, state, interpret=interpret)
