"""JAX's persistent compilation cache, switched on by the entry points.

Entry points (``launch/serve.py``, ``benchmarks/run.py``, ``chip_smoke.py``)
call :func:`enable_compile_cache` before they compile anything; importing
this module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# A fixed path inside the checkout (git-ignored): the cache key includes
# the directory, so a path that moved between runs would never hit.
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Cache every compiled program on disk and return the directory:
    ``JAX_COMPILATION_CACHE_DIR`` where it is set, else
    :data:`CHECKOUT_CACHE_DIR`."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CHECKOUT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    # the defaults skip programs that compile in under a second; a serving
    # run compiles many small ones (samplers, relayouts) on every start
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
