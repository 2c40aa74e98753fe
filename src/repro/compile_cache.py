"""JAX's persistent compilation cache, at one fixed place per checkout.

Entry points (benchmarks, scripts, ``chip_smoke.py``) call
``enable_compile_cache()`` before their first compile; library modules
never do. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it by
itself and nothing is set here. Otherwise the cache lives in
``<checkout>/.jax_cache`` (listed in ``.gitignore``): the directory is
part of the cache key, so it must not move between runs.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
