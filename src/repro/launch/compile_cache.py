"""Where JAX keeps its persistent compilation cache.

Entry points call :func:`place_compile_cache` from ``main()`` — never at
import — before their first compile.  The cache key includes its path, so
the directory is fixed: ``$JAX_COMPILATION_CACHE_DIR`` when set (JAX reads
it itself, and nothing is set here), else ``.jax_cache/`` at the checkout
root (gitignored).
"""
from __future__ import annotations

import os

import jax

CHECKOUT_ROOT = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", ".."))


def place_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(CHECKOUT_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
