"""Placement of JAX's persistent compilation cache (a copy of the
program's ``launch/compile_cache.py``, so that the benchmark's cache
does not move when the program's does).

Where ``JAX_COMPILATION_CACHE_DIR`` is set, that directory stays in
charge.  Otherwise the cache is ``.jax_cache/`` at the checkout root: a
fixed path, because the path is part of the cache key.
"""

from __future__ import annotations

import os
import pathlib

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[1] / ".jax_cache"


def place_compile_cache() -> str:
    """Return the cache directory in use, setting it when the environment
    names none."""
    import jax
    # Every program of a cell goes into the cache, however quick its
    # compile, so that a second run of the cell compiles nothing.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
