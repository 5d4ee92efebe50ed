"""JAX's persistent compilation cache, for the repo's entry points.

Each entry point (``repro.launch.serve``, ``chip_smoke.py``,
``examples/spatial_serve.py``) calls ``enable_compile_cache`` once at
start-up; importing this module changes nothing.
"""
from __future__ import annotations

import os

import jax

# <repo>/.jax_cache: fixed, so one checkout finds its own entries again
# (the path is part of the cache key); git-ignored
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    this sets no other directory; otherwise the cache lives in
    ``DEFAULT_DIR`` inside the checkout.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
