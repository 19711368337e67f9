"""Where JAX keeps its persistent compilation cache.

A cache entry's key includes the cache directory, so a directory that moves
between runs (a temp, pid or time path) never hits.  Entry points that
compile at real size (``chip_smoke.py``, ``benchmarks/run.py``) call
:func:`use_persistent_cache` once, before their first compile.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/.jax_cache: this file is <checkout>/src/repro/compile_cache.py
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_persistent_cache() -> str:
    """Return the persistent compilation cache directory in use.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it by itself and
    nothing is changed here; otherwise the cache goes to the fixed
    ``<checkout>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
