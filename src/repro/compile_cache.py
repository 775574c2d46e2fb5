"""Where JAX keeps its persistent compilation cache.

The path is part of the cache's key, so it is fixed:
``$JAX_COMPILATION_CACHE_DIR`` when that is set (JAX reads the variable
itself), else ``.jax_cache/`` at the root of the checkout.  Entry points
call :func:`configure_compile_cache` before their first compile; tests do
not.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
