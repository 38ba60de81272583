"""JAX's persistent compilation cache, at one fixed place.

A cache entry is keyed on its directory, so a directory that moves
between runs never hits.  :func:`enable_compile_cache` is the first call
of every command that compiles for the chip (``chip_smoke.py``,
``benchmarks/run.py``).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory:
    ``$JAX_COMPILATION_CACHE_DIR`` where that is set, else the repository's
    git-ignored ``.jax_cache``.  Every program is cached, however quick to
    compile, since a chip run pays each compile anew."""
    path = os.environ.get(ENV_VAR) or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
