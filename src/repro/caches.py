"""Checkout-local caches: JAX's persistent compile cache and the tile cache.

Both live at fixed paths inside the checkout (gitignored), never under a
temp name, a pid or a time: a cache keyed by a path that moves never hits.

* Compile cache — :func:`enable_compile_cache`. Where
  ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this module
  sets nothing; otherwise entry points that run on the chip point JAX at
  ``<checkout>/.jax_cache``, so a second run in the same checkout reuses
  the first run's executables.
* Tile cache — :data:`TILE_CACHE_PATH`, the measured autotuner's default
  on-disk cache (``kernels/tuning.py``; ``REPRO_TUNE_CACHE`` overrides it).
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[2]
COMPILE_CACHE_DIR = CHECKOUT / ".jax_cache"
TILE_CACHE_PATH = CHECKOUT / ".tile_cache.json"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compile cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    return str(COMPILE_CACHE_DIR)
