"""The persistent compile cache that every entry point uses.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
changes nothing.  Otherwise the cache goes to ``.jax_cache/`` at the root
of the checkout (git ignores it): a fixed path, since the path is part of
the cache key and a directory that moves never hits.  Entry points call
:func:`enable_compile_cache` first thing; importing this module sets
nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it writes to."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
