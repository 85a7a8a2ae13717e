"""JAX's persistent compilation cache, at a path that does not move.

The cache key includes the directory, so a cache that moves never hits.
The entry points (``launch/serve.py``, ``launch/server.py``,
``chip_smoke.py``) call :func:`enable_compile_cache` once before their
first compile.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# <repo root>/.jax_cache (git-ignored): this file is
# <repo>/src/repro/launch/compile_cache.py
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it by
    itself and nothing is set here.  Otherwise the cache lives at the
    fixed ``<repo root>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
