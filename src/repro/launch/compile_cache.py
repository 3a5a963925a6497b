"""JAX's persistent compilation cache, kept at one fixed place.

A chip run pays for every compile. JAX reuses a compiled program across
processes when its persistent cache is on, and the cache directory is part
of each entry's key, so it must not move between runs.
"""
from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["enable_compile_cache", "DEFAULT_DIR"]

# <checkout>/.jax_cache (listed in .gitignore); this file is
# <checkout>/src/repro/launch/compile_cache.py
DEFAULT_DIR = str(pathlib.Path(__file__).resolve().parents[3] / ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, where set, is honoured as JAX reads it,
    and no other path is set in code. Otherwise the cache lives in the
    checkout's ``.jax_cache``.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
