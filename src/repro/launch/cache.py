"""Persistent compilation cache location.

One rule for every program that compiles for the chip (``chip_smoke.py``
and the benchmarks): when ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
reads it itself and nothing here overrides it; otherwise the cache
lives in ``.jax_cache`` at the repository root. The path is fixed
(never derived from a temp name, a pid or the time) because it is part
of the cache key: a directory that moves never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["REPO_CACHE_DIR", "enable_compilation_cache"]

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compilation_cache() -> str:
    """Point JAX's persistent compilation cache at its one location and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
