"""JAX's persistent compilation cache, placed once for every entry point.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
here overrides it. Otherwise the cache goes to ``<checkout>/.jax_cache``, a
fixed path (the path is part of the cache key, so a directory that moves
never hits) that ``.gitignore`` lists. The CLIs call :func:`enable` from
their ``__main__`` guard, so importing them, as the tests do, leaves the
cache off.
"""
from __future__ import annotations

import os

import jax

CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE)
    return CHECKOUT_CACHE
