"""JAX's persistent compilation cache for the benchmark's processes.

It lives in ``.jax_cache`` at the root of the checkout, a fixed path (the
path is part of the cache's key), so only the first run of a cell in a
checkout compiles. The directory is handed to JAX, and to the program,
through ``JAX_COMPILATION_CACHE_DIR``, which the program's own entry points
take when it is set; so :func:`enable` runs before anything imports JAX.
"""
from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def enable() -> str:
    """Point JAX's cache at the checkout's directory, caching every
    program however quickly it compiled; returns the directory."""
    if "jax" in sys.modules:
        raise RuntimeError("the compile cache is set before JAX is imported")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return CACHE_DIR
