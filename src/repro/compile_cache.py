"""JAX's persistent compilation cache, placed where a later run finds it."""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here.  Otherwise the cache lives at the fixed
    ``<checkout>/.jax_cache``: the directory is part of what makes a
    later run find an entry, so it is never temporary or per-process.
    Entry points call this (the service's ``main``, ``chip_smoke.py``,
    the benchmarks); importing the library never does.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
