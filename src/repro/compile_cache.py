"""JAX's persistent compilation cache for the repo's entry points.

A chip run compiles the solver's kernels and jitted steps from nothing
unless an earlier process left them in a persistent cache.  The cache key
includes the directory, so it only hits when the directory does not move:

* if ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
  here changes it;
* otherwise entry points (``chip_smoke.py``, ``benchmarks/run.py``) call
  :func:`enable_compile_cache`, which points JAX at the fixed
  ``.jax_cache/`` directory of this checkout (listed in ``.gitignore``).

Importing the library never touches the cache setting.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
