"""Where JAX keeps its persistent compilation cache.

The cache's key includes its directory, so a directory that moves between
runs never hits.  ``use_compile_cache`` keeps it in one fixed place: where
``JAX_COMPILATION_CACHE_DIR`` says when that is set (JAX reads the
variable itself, so nothing is configured here), and otherwise in
``.jax_cache`` at the root of this checkout (ignored by git).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory and
    return that directory.  Call before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
