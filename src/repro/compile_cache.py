"""JAX's persistent compilation cache, for the entry points.

Importing this module sets nothing; an entry point calls
:func:`enable_compile_cache` once, before its first compile.
"""
from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["enable_compile_cache"]

# The directory is part of what the cache is keyed on, so it is fixed: a
# temporary, pid- or time-named directory would never be hit again.
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, already names the directory
    (JAX reads it at start-up) and is left alone.  Otherwise the cache goes
    to ``<repo>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
