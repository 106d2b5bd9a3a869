"""JAX's persistent compilation cache, kept at one fixed place.

A chip run compiles every serving graph from scratch unless an earlier
process left them in the cache, and the cache directory is part of what a
later process must find: a path built from a temp name, a pid or the time
never hits. So the directory is ``$JAX_COMPILATION_CACHE_DIR`` when that is
set (JAX reads the variable itself) and ``<checkout>/.jax_cache``
otherwise.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# src/repro/launch/compile_cache.py -> the checkout root
CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
