"""Where JAX keeps its persistent compilation cache.

A deployment places the cache with ``JAX_COMPILATION_CACHE_DIR``; without
it the cache lives at a fixed path inside the checkout (``.jax_cache``,
git-ignored). The path is part of the cache's key, so it is never built
from a temp name, a pid or the time. Call :func:`enable_compile_cache`
before the process's first compile.
"""
from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at ``$JAX_COMPILATION_CACHE_DIR``
    if set, else at :data:`DEFAULT_DIR`. Returns the directory."""
    import jax

    path = os.environ.get(ENV_VAR) or DEFAULT_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    return path
