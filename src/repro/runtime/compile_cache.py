"""Where the entry points keep JAX's persistent compilation cache.

The cache is keyed by the directory it lives in, so a path that moves
between runs (a temp dir, a pid, a time stamp) never hits. Entry points call
:func:`enable_compile_cache` once, in ``main``; importing this module
changes nothing.
"""
from __future__ import annotations

import os

import jax

REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is where JAX already keeps the
    cache: it is left as it is and no other directory is set. Otherwise the
    cache goes to ``.jax_cache`` at the root of the checkout."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
