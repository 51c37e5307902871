"""JAX's persistent compilation cache, kept at one fixed place.

Compiling the search plans, the DBSCAN sweeps and the serving programs is a
large part of a cold run.  A later process finds them again only when it
looks in the same directory, so the directory never moves: the one JAX
reads from ``JAX_COMPILATION_CACHE_DIR`` when that is set, else
``.jax_cache/`` at the root of the checkout.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:  # JAX already reads this variable itself
        return path
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
