"""Where JAX's persistent compilation cache lives.

One helper, called by the entry points (``chip_smoke.py``, the serving
CLI, the benchmarks) and never at import: a library import must not move
a process's cache.  The cache directory is part of each entry's lookup, so
it is a fixed path, never a temporary name.
"""
from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

#: Used when ``JAX_COMPILATION_CACHE_DIR`` is unset: ``.jax_cache`` at the
#: root of the checkout (listed in ``.gitignore``).
CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as is (JAX reads it
    itself); otherwise the cache goes to :data:`CHECKOUT_CACHE_DIR`.
    Every compile is cached, however short: a cold chip run spends most of
    its set-up compiling many small kernels.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
