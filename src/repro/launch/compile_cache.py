"""JAX's persistent compilation cache, at a path that stays put.

A cache entry is only found again from the same directory, so the directory
is fixed: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (JAX
reads that variable itself), else ``<checkout>/.jax_cache``.  Called by the
launchers and ``chip_smoke.py`` before they compile anything — never at
import.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# src/repro/launch/compile_cache.py -> the checkout root
CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
