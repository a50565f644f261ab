"""Persistent compilation cache for the entry points.

``train.main``, ``chip_smoke.py`` and the ``benchmarks/*`` mains call
:func:`enable_compile_cache` first, so a process reuses what an earlier
one compiled. Library modules and tests never touch the cache.
"""
from __future__ import annotations

import os

import jax

#: the fixed fallback: a cache directory must not move between runs,
#: because its path is part of every entry's key
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory: ``JAX_COMPILATION_CACHE_DIR`` where it is set (JAX reads
    it itself), otherwise ``<repo root>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
