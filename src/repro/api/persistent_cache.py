"""JAX's persistent compilation cache, for the entry points.

Compiling a full-width model takes minutes on a TPU; the persistent cache
lets a later process on the same machine load the programs instead.  The
entry points (``chip_smoke.py``, ``repro.launch.serve``,
``benchmarks.run``) call :func:`enable_persistent_cache` once, before
anything compiles.  Library code never touches the cache.
"""
from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["CACHE_DIR", "enable_persistent_cache"]

#: the cache directory used when ``JAX_COMPILATION_CACHE_DIR`` is unset:
#: fixed inside the checkout (and git-ignored), because the path is part
#: of what a cached entry is found by
CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_persistent_cache() -> str:
    """Turn on the persistent cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here; otherwise the cache goes to :data:`CACHE_DIR`.
    """
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
