"""JAX's persistent compilation cache, placed from outside.

Every entry point (``chip_smoke.py``, the launchers, the benchmarks and
the examples) calls :func:`enable_compile_cache` before its first compile.
Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and this
sets no other directory. Otherwise the cache goes to ``.jax_cache`` at the
root of the checkout: a fixed path, because the path is part of every
cache key and a directory that moves never hits.
``JAX_ENABLE_COMPILATION_CACHE=false`` keeps the cache off; the tests that
start an entry point in a subprocess set it.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

import jax

REPO_ROOT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> Optional[str]:
    """Turn the persistent compilation cache on; returns its directory, or
    None where ``JAX_ENABLE_COMPILATION_CACHE=false`` keeps it off."""
    if not jax.config.jax_enable_compilation_cache:
        return None
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    return path
