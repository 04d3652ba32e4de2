"""JAX's persistent compilation cache, for the repo's entry points.

`enable_compile_cache()` is called by the command-line entry points
(`chip_smoke.py`, `benchmarks/run.py`, `python -m repro.launch.multihost`)
before they compile anything, and never at library import.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is
overridden.  Otherwise the cache lives in a fixed ``.jax_cache/`` at the
repository root: the directory is part of every entry's key, so it must
not move from one run to the next.
"""
from __future__ import annotations

import os
from pathlib import Path

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
