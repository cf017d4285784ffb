"""Where the entry points keep JAX's persistent compilation cache."""
from __future__ import annotations

import os
import pathlib

import jax

# one fixed directory inside the checkout: a cache whose path moves never
# hits, so it is never built from a temporary name, a pid or the time
CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads the variable
    itself and this sets nothing; otherwise the cache goes to
    :data:`CACHE_DIR`.  Call it before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
