"""JAX's persistent compilation cache, placed from outside or at a fixed path.

Every entry point calls ``enable_compile_cache()`` at the start of its
``main`` (never at import time).  Where ``JAX_COMPILATION_CACHE_DIR`` is
set, JAX reads that variable itself and nothing is set here.  Otherwise
the cache lives in ``.jax_cache/`` at the root of the checkout: a fixed
path, because the path is part of the cache key, so a directory that moves
never hits.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: ``<checkout>/.jax_cache`` (this file is ``<checkout>/src/repro/launch/``).
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
