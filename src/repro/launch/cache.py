"""Persistent XLA compilation cache for the entry points.

Each entry point (``launch/peel.py``, ``launch/hserve.py``,
``launch/stream.py``, ``chip_smoke.py``) calls
:func:`enable_compile_cache` once at start-up; importing a library module
never does.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and nothing is set here.  Otherwise the cache lives at a fixed
path inside the checkout: the directory is part of what a later run
must find again, so it never depends on a temporary name, a pid or the
time.
"""
from __future__ import annotations

import os
from pathlib import Path

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

# <checkout>/.jax_cache (this file is <checkout>/src/repro/launch/cache.py)
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
