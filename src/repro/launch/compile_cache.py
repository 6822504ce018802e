"""JAX's persistent compilation cache for the program's entry points.

Call :func:`enable_compile_cache` first thing in an entry point's ``main``
(never at import).  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads
it itself and nothing is set here.  Otherwise the cache goes to
``<checkout>/.jax_cache`` — one fixed directory, ignored by git, so a rerun
from the same checkout finds what an earlier run compiled.
"""
from __future__ import annotations

import os
import pathlib

import jax

CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it writes to."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
