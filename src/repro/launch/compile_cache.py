"""Where JAX keeps its persistent compilation cache.

A cold run on the chip compiles every program, which is a large part of
its wall time. The cache directory is part of the cache key, so it must
not move between runs: never a temporary, per-process or timestamped
path.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout's own cache, used when the environment names none
REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here; otherwise the cache goes to ``<repo>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
