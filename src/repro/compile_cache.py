"""JAX's persistent compilation cache at a fixed place.

Entry points call ``enable_compile_cache()`` before their first compile.
Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and nothing
else is configured; otherwise the cache lives in ``.jax_cache`` at the root
of the checkout, a path that does not change between runs, so a later run
on the same machine finds what an earlier one compiled."""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    # kernels compile in well under JAX's default 1 s floor; cache them too
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
