"""JAX's persistent compilation cache for the program's entry points.

``JAX_COMPILATION_CACHE_DIR``, where it is set, is read by JAX itself and
nothing here overrides it.  Otherwise the cache lives at a fixed
``.jax_cache/`` in the checkout (git-ignored): the directory is part of
the cache key, so it is never derived from a temp name, a PID or a time.
Entry points call ``enable_compile_cache()``; library code and tests
never do.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
