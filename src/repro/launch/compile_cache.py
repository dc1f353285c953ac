"""JAX's persistent compilation cache at a fixed place.

A cache directory is part of what a cached program is found by, so it must
not move between runs: ``JAX_COMPILATION_CACHE_DIR`` where the environment
sets it (JAX reads it itself), else ``.jax_cache`` at the root of the
checkout.  Entry points call :func:`enable_compile_cache` from ``main``;
importing this module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
