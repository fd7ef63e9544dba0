"""Thin mesh / shard_map helpers shared by the runtime, tests and examples.

``make_mesh`` builds a mesh whose axes are all ``AxisType.Auto`` (JAX's own
``make_mesh`` now defaults to ``Explicit``, which the GSPMD steps do not
use); ``shard_map`` names the manual axes and turns the VMA check off by
default; ``axis_size`` reports 1 for an axis that is None or unbound, so
collectives written for a tier degrade to no-ops on a mesh without it.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax import lax
from jax.sharding import AxisType


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str], *,
              devices: Optional[Sequence] = None):
    """``jax.make_mesh`` with all axes Auto."""
    shape = tuple(axis_shapes)
    return jax.make_mesh(shape, tuple(axis_names),
                         axis_types=(AxisType.Auto,) * len(shape),
                         devices=devices)


def shard_map(f, *, mesh=None, in_specs, out_specs, axis_names=None,
              check_vma: bool = False):
    """``jax.shard_map``; ``axis_names`` are the MANUAL axes (None = all
    mesh axes)."""
    kw = {"axis_names": set(axis_names)} if axis_names is not None else {}
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma, **kw)


def axis_size(axis_name) -> int:
    """Static size of a bound manual axis; 1 for None/unbound names."""
    if axis_name is None:
        return 1
    try:
        return lax.axis_size(axis_name)
    except NameError:
        return 1
