"""JAX's persistent compilation cache, in one fixed place per checkout.

``enable()`` is called by every launch CLI and by ``chip_smoke.py``
before their first compile.  Where ``JAX_COMPILATION_CACHE_DIR`` is set,
JAX already caches there and nothing is changed.  Otherwise the cache
goes to ``<checkout>/.jax_cache`` (git-ignored).  The path must not
move between runs: it is part of the cache key, so a temporary or
per-process directory would never be hit.
"""
from __future__ import annotations

import os

CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
