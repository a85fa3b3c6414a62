"""Where JAX keeps its persistent compilation cache.

Entry points call ``enable_compile_cache()`` once, before their first
compile; importing this module changes nothing.  When the environment sets
``JAX_COMPILATION_CACHE_DIR``, JAX reads it itself and this sets nothing.
Otherwise the cache goes to ``<checkout>/.jax_cache`` (git-ignored): a
fixed path, because the directory is part of the cache key, so a path
built from a temporary name, a process id or the time would never hit.
"""
from __future__ import annotations

import os

import jax

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
