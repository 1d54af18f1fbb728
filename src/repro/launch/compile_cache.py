"""Persistent XLA compilation cache, placed from outside or at a fixed path.

Every entry point that compiles for the chip calls :func:`setup_compile_cache`
before its first compile, so the processes of one run share compiled
programs.  If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its
cache there and nothing is set here.  Otherwise the cache goes to
``.jax_cache/`` at the checkout root: a fixed path, because the path is part
of the cache key and a moving directory never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: Cache directory used when ``JAX_COMPILATION_CACHE_DIR`` is not set.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
