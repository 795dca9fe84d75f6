"""JAX persistent compilation cache for the entry points.

Called by ``chip_smoke.py`` and ``launch/serve.py`` before their first
compile; importing ``repro`` never turns the cache on.  The directory is
part of the cache key, so it must not move between runs: when
``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and nothing here
overrides it; otherwise the cache lives at one fixed, git-ignored path in
the checkout.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
