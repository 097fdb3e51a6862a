"""JAX's persistent compilation cache for the entry points.

Call :func:`enable_compile_cache` at the start of a program, before its
first compile; never at import.  Where ``JAX_COMPILATION_CACHE_DIR`` is
set, that directory is the cache.  Otherwise the cache lives at a fixed
path inside the checkout (:data:`DEFAULT_DIR`, git-ignored): the path is
part of what a cache entry is found by, so it never moves between runs.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
