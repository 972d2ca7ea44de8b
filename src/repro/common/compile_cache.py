"""JAX's persistent compilation cache, kept where the next run finds it.

The switch plane compiles one program per (engine mode, shape bucket,
device) and one per distinct scan capacity, each well under a second, so
a cold process spends much of its start-up compiling.  Entry points
(``chip_smoke.py``, ``benchmarks/bench_*.py``) call
``enable_compile_cache()`` first; nothing calls it at import.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# one fixed directory in the checkout (listed in .gitignore), never named
# after a temporary path, a PID or the time
CHECKOUT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on for this process and return its
    directory.  ``JAX_COMPILATION_CACHE_DIR``, when set, is the directory
    (JAX reads it itself; no other is set).  Otherwise the checkout's
    ``.jax_cache``.  Every program is cached, however short its compile:
    the default one-second threshold would skip the engine buckets."""
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(CHECKOUT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # JAX's default also turns on XLA's GPU autotune cache, whose path
    # (under the cache directory) goes into every cache key: a cache that
    # was moved or copied would then never hit.  Nothing here runs on a GPU
    jax.config.update("jax_persistent_cache_enable_xla_caches", None)
    return path
