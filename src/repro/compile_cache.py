"""Where JAX's persistent compilation cache lives.

A later run finds an earlier run's programs only if both use the same
directory, so the path is fixed: never a temporary, per-process or
time-stamped one.  ``JAX_COMPILATION_CACHE_DIR`` set by whoever runs the
program wins and JAX reads it itself; otherwise the cache goes to the
``.jax_cache`` directory at the root of the checkout (git-ignored).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its place; returns it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
