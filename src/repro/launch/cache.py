"""Persistent compilation cache for the entry points.

JAX keys a cache entry on its directory path, so the path is fixed: the
``JAX_COMPILATION_CACHE_DIR`` environment variable when it is set (JAX
reads it itself and this module sets nothing), otherwise ``.jax_cache``
at the root of the checkout.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: Root of the checkout (src/repro/launch/cache.py -> three levels up).
REPO_ROOT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory
    and return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(REPO_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
