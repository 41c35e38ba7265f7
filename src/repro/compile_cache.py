"""JAX's persistent compilation cache, kept at one fixed place.

The cache key includes the directory, so a path that moves (a temporary
name, a process id, a time) never hits.  ``JAX_COMPILATION_CACHE_DIR``
wins where it is set: JAX reads it itself and nothing is set here.
Otherwise the cache lives in ``.jax_cache/`` at the root of the checkout.
"""
from __future__ import annotations

import os

import jax

CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory.

    Every compiled program is cached, however quick its compile: a
    query wave compiles many small programs (one per segment and bucket
    shape), and together they are most of a cold start."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = CHECKOUT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
