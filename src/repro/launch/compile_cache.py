"""JAX's persistent compilation cache at one fixed place per checkout.

The cache key includes the directory, so a path that moves never hits:
no temp names, pids or timestamps here.
"""
from __future__ import annotations

import os

import jax

# <checkout>/.jax_cache (git-ignored); this file is src/repro/launch/*.py
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on before the first compile.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is set here; otherwise the cache goes to ``CACHE_DIR``.
    Returns the directory in use.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
