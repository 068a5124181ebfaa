"""JAX's persistent compilation cache for the entry points.

Compiling one discovery step for a TPU at full graph width takes tens of
seconds, so the programs a process compiles are kept on disk and found
again by the next process.  A later process finds them only where it
looks, so the directory never varies between runs:
``JAX_COMPILATION_CACHE_DIR`` where it is set, and otherwise one fixed
directory inside the checkout.

Entry points (``launch/serve.py``, ``benchmarks/run.py``, ``chip_smoke.py``)
call :func:`enable_compile_cache` once at start.  Importing this module
changes nothing, and tests never call it.
"""
from __future__ import annotations

import os

import jax

# <repo>/.jax_cache: this file is <repo>/src/repro/runtime/compile_cache.py
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..",
    ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    path = os.path.normpath(os.environ.get("JAX_COMPILATION_CACHE_DIR")
                            or CHECKOUT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    # A Pallas TPU kernel is embedded as serialized Mosaic IR that keeps
    # the source locations of its trace, absolute file paths included;
    # the cache key strips debug info from the outer program only.  With
    # no traceback locations a kernel program gets the same key from any
    # checkout directory.
    jax.config.update("jax_traceback_in_locations_limit", 0)
    return path
