"""One place that decides where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``examples/*``, ``benchmarks/run.py``) call
:func:`use_compile_cache` before their first jit.  If
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is
set here.  Otherwise the cache goes to ``<checkout>/.jax_cache``: a fixed
path, because the directory is part of the cache key — a path that moved
between runs would never hit.  The directory is git-ignored.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
