"""JAX's persistent compilation cache, placed from outside or at one fixed
path in the checkout.

The cache key includes the directory, so a path that moves between runs
(a temporary directory, a pid, a timestamp) never hits.  Call
:func:`enable_compile_cache` from an entry point, never at import.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_DIR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on and return its directory.

    Where ``$JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here; otherwise the cache is ``<repo>/.jax_cache``.
    """
    if os.environ.get(ENV_DIR):
        return os.environ[ENV_DIR]
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
