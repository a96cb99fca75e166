"""JAX's persistent compilation cache for the entry points.

``enable_compile_cache()`` is called by the entry points (``serve.py``,
``chip_smoke.py``, ``benchmarks/run.py``) before they compile, never on
import.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it
and the cache lives there alone; otherwise the cache is the fixed
directory ``<checkout>/.jax_cache`` (git-ignored).  The path is part of
the cache's key, so it never comes from a temporary name, a pid or the
clock.  Every compile is written, however short: a cold replica pays no
compile that an earlier process already paid.

This is JAX's own executable cache.  ``repro.kernels.compile_cache`` is
something else: a table that prices compile time on the simulated clock.
"""
from __future__ import annotations

import os

import jax

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir
