"""JAX's persistent compilation cache, at a path that does not move.

The cache key includes the cache directory, so a directory that changes from
run to run (a temporary name, a pid, a time) never hits. Call
:func:`enable_compile_cache` once, before the first compilation.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/.jax_cache: src/repro/launch/compile_cache.py is four levels down
CHECKOUT_CACHE_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is set here. Otherwise the cache goes to ``.jax_cache`` in
    the checkout (listed in ``.gitignore``)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
