"""JAX's persistent compilation cache, in one fixed place.

A cold run of the engine compiles one executable per (program, batch
bucket), each holding many per-tile kernels; the persistent cache lets
the next process on the same tree load them instead.  Its directory is
part of the cache key, so it must not move between runs: it is
`$JAX_COMPILATION_CACHE_DIR` when that is set (JAX reads the variable
itself, and nothing here overrides it), else `<repo>/.jax_cache`.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Call from an entry point before the first compile, never at import."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
