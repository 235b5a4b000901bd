"""JAX persistent compilation cache for the serving entry points.

The cache key includes the cache path, so the directory must not move
between runs: `JAX_COMPILATION_CACHE_DIR` when it is set (JAX reads it
itself and nothing is set in code), otherwise the fixed `.jax_cache/` at
the root of the checkout (listed in `.gitignore`).
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def cache_dir() -> str:
    """The directory compiled programs are cached in."""
    return os.environ.get(ENV_VAR) or str(DEFAULT_DIR)


def enable_compile_cache() -> str:
    """Turn the persistent cache on for every compile of this process;
    returns its directory."""
    import jax

    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    # cache every program: serving compiles many small step graphs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return cache_dir()
