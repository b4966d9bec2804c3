"""Where the entry points keep JAX's persistent compilation cache.

Importing the library sets nothing: `chip_smoke.py` and `benchmarks.run`
call `use_compile_cache()` at start-up.
"""

from __future__ import annotations

import os
import pathlib

import jax

# <checkout>/.jax_cache (this file is <checkout>/src/repro/compile_cache.py):
# a fixed path, so a later run of the same checkout finds what an earlier
# one compiled
CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
    sets no other directory. Otherwise the cache goes to
    ``<checkout>/.jax_cache``.
    """
    outside = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if outside:
        return outside
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
