"""JAX's persistent compile cache at a place that does not move.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``) call
``use_compile_cache`` once before their first compile; importing the
library never turns the cache on, so the tests compile fresh.

The cache key includes the directory, so the default is a fixed path
inside the checkout (``<repo>/.jax_cache``, listed in ``.gitignore``),
never a temporary name. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
reads it itself and this function changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compile cache at ``JAX_COMPILATION_CACHE_DIR``
    when set, else at ``CACHE_DIR``; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
