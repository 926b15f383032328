"""JAX persistent compilation cache for the entry-point scripts.

A cold step at real fanouts launches one kernel per (K rung x SMEM chunk x
layer), so a cold process compiles many programs. The cache keeps them
across processes. Its directory is part of the cache key, so it never
moves: ``$JAX_COMPILATION_CACHE_DIR`` when that is set (JAX reads it
itself, and nothing here overrides it), else ``<checkout>/.jax_cache``.

Call :func:`enable` from a script's ``main`` only — never at library import
and never from the tests.
"""

from __future__ import annotations

import os

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CACHE_DIRNAME = ".jax_cache"


def enable(checkout: str) -> str:
    """Turn the persistent compilation cache on; return its directory."""
    import jax

    if os.environ.get(CACHE_ENV):
        return os.environ[CACHE_ENV]
    path = os.path.join(os.path.abspath(checkout), CACHE_DIRNAME)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
