"""JAX's persistent compilation cache, as the entry points turn it on.

``chip_smoke.py``, the examples and the benchmark entry points call
``enable_compile_cache()`` once, at start-up; importing the library never
does. A cold run compiles every kernel and jitted step, which on a TPU can
be a large share of a short run; a warm cache skips those compiles.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

# A fixed directory inside the checkout (listed in .gitignore). The path is
# part of what lets a later run find the entries, so it never depends on a
# temporary name, a process id or the time.
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads that
    directory and this sets no other. Otherwise the cache lives in
    ``CHECKOUT_CACHE_DIR``. The size and compile-time thresholds are
    dropped, so the Pallas kernels, which compile in well under JAX's
    default one second, are cached too.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path
