"""Where JAX keeps its persistent compile cache for this checkout.

JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself: when it is set, that
directory is the cache and nothing here overrides it.  Otherwise the
cache goes to ``.jax_cache`` at the root of the checkout (git-ignored).
The path is fixed, never built from a temp name, a pid or the time,
because a later process finds its compiled programs only under the same
path.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compile cache at its directory (see the
    module docstring) and return that directory."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
