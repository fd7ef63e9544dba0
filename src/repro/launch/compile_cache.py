"""Where JAX keeps its persistent compilation cache.

The one place in the repository that sets a cache directory.  Entry points
(``chip_smoke.py``, ``repro.launch.train``, ``repro.launch.serve``) call
:func:`use_compile_cache` before their first compile.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: default cache directory: fixed, because the path is part of the cache
#: key — a directory that moves never hits (listed in ``.gitignore``)
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    this leaves it alone; otherwise the cache goes to ``<checkout>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
