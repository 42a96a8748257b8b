"""JAX's persistent compilation cache, for the entry points.

Call :func:`use_compile_cache` from an entry point's ``main`` before the first
compile, never from a library module while it is imported.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# the root of the checkout: src/repro/launch/ -> three levels up
_CHECKOUT_ROOT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    other directory is set here.  Otherwise the cache is ``.jax_cache/`` at
    the root of the checkout: a fixed path, because the path is part of what
    a later run must find again."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(_CHECKOUT_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
