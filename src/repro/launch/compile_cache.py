"""Where JAX's persistent compilation cache lives.

The cache is keyed by path as well as by program, so it only hits when the
path is the same on every run.  ``JAX_COMPILATION_CACHE_DIR``, when set, is
read by JAX itself and wins; otherwise the cache goes to one fixed,
git-ignored directory inside the checkout.  Entry points call
:func:`enable_compile_cache` from ``main()``; importing this module changes
nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
