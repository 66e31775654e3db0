"""Where the program keeps JAX's persistent compilation cache.

Entry points that compile for a device (`chip_smoke.py`, the benchmark
mains) call `enable_compile_cache()` before their first compile; tests do
not. The cache directory is part of the cache key, so it is a fixed path:
`JAX_COMPILATION_CACHE_DIR` when the environment sets it (JAX reads it
itself), else `<checkout>/.jax_cache`.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
