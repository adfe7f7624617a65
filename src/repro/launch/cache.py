"""JAX's persistent compilation cache for the entry points."""
from __future__ import annotations

import os
from pathlib import Path

import jax

# one fixed directory inside the checkout: the cache key includes the path,
# so a directory named after a pid, a temp name or the time would never hit
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Keep compiled programs across runs. Where `JAX_COMPILATION_CACHE_DIR`
    is set, JAX reads it itself and this sets nothing; otherwise the cache
    is `CHECKOUT_CACHE`. Returns the directory in use."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return jax.config.jax_compilation_cache_dir
