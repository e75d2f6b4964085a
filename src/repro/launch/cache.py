"""JAX's persistent compilation cache, set in one place for every entry
point that runs on the chip (``chip_smoke.py``, ``launch/train.py``,
``launch/serve.py``).

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
sets no other path. Otherwise the cache lives at a fixed directory inside
the checkout (``<repo>/.jax_cache``, git-ignored): the directory is part
of the cache key, so it is never built from a temp name, a pid or a time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compilation_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
