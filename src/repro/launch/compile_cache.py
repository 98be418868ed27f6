"""JAX persistent compilation cache for the repo's entry scripts.

`JAX_COMPILATION_CACHE_DIR`, when set, is JAX's own setting and wins: the
cache lives there and nothing here overrides it. Otherwise the cache lives
in ``<checkout>/.jax_cache`` (listed in .gitignore), a fixed path, because
the path is part of the cache key: a directory that moves never hits.
"""
from __future__ import annotations

import os

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache(checkout: str) -> str:
    """Turn on the persistent cache; return the directory it uses. Call
    before the first compilation. Every program is cached, however quickly
    it compiled: the fused driver's many small programs add up."""
    import jax

    path = os.environ.get(CACHE_ENV)
    if not path:
        path = os.path.join(os.path.abspath(checkout), ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


__all__ = ["CACHE_ENV", "enable_compile_cache"]
