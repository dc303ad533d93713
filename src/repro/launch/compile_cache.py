"""JAX's persistent compilation cache at one fixed place.

Entry points (the launchers, ``chip_smoke.py``) call
:func:`enable_compile_cache` once before they compile anything; library
modules never do, so importing them leaves JAX's configuration alone.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# src/repro/launch/compile_cache.py -> the checkout root
CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and no
    other directory is set here. Otherwise the cache lives in ``.jax_cache/``
    inside the checkout: a fixed path, because the path is part of what a
    cached entry is found by.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
