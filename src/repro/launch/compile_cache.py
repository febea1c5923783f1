"""Persistent JAX compilation cache at a path that does not move.

A 24-layer serving step takes tens of seconds to compile.  The
persistent cache keys entries by the cache directory among other things,
so it only pays when every run uses the same directory:

* ``JAX_COMPILATION_CACHE_DIR`` set: that directory, and no other;
* otherwise ``.jax_cache/`` at the root of this checkout (git-ignored).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return the path."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CHECKOUT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
