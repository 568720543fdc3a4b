"""JAX's persistent compilation cache for the launchers.

Compiling the warmed template set dominates a cold start at real widths,
so every entry point turns the cache on before its first compile.  The
directory is part of what lets a later run hit, so it is fixed: the one
``JAX_COMPILATION_CACHE_DIR`` names, or ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import os

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory.
    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is set."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
