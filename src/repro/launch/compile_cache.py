"""Where JAX keeps its persistent compilation cache.

The cache key includes the directory, so the directory must not move between
runs: a path built from a temp name, a pid or the time never hits. When the
deployment places the cache itself (``JAX_COMPILATION_CACHE_DIR``), JAX reads
that variable on its own and nothing is set here. Otherwise the cache lives in
one fixed, git-ignored directory of the checkout.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.
    Call once at an entry point, before the first compile."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
