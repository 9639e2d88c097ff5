"""JAX's persistent compilation cache for this repository's entry points.

Called once at start-up by the scripts that run on the chip
(`chip_smoke.py`, `benchmarks/run.py`), never at library import. Where
`JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and nothing is
configured here. Otherwise the cache lives at the fixed
`<repo>/.jax_cache`: the directory is part of the cache key, so a path
that moved between runs would never hit.
"""
from __future__ import annotations

import os

import jax

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
CACHE_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def init_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
