"""JAX's persistent compilation cache, placed from outside.

Entry points call `enable_compilation_cache()` from their `main()`;
importing this module changes nothing.
"""
from __future__ import annotations

import os
import pathlib

import jax

# fixed path: the directory is part of the cache key, so it must not
# move between runs of one checkout
CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compilation_cache() -> str:
    """Keep compiled programs across processes; returns the directory.

    `JAX_COMPILATION_CACHE_DIR`, when set, is the cache (JAX reads the
    variable itself, so no other directory is set here); otherwise the
    cache is `.jax_cache/` at the root of this checkout."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
