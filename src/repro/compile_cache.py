"""JAX's persistent compilation cache, kept at one fixed place.

A process that reaches the device compiles every PDHG stage program and every
scoring-kernel shape it meets.  With the persistent cache on, the next process
reads them back instead.  The cache only hits when its directory stays put, so
the default is a fixed directory inside the checkout, never a temporary one.
"""

from __future__ import annotations

import os
import pathlib

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

# <checkout>/.jax_cache (this file is <checkout>/src/repro/compile_cache.py)
CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting: it is left
    alone and no other directory is set.  Otherwise the cache goes to
    :data:`CHECKOUT_CACHE_DIR`.  Call before the first compile.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    CHECKOUT_CACHE_DIR.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
