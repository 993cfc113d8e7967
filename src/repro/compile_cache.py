"""JAX's persistent compilation cache, placed for this checkout.

Entry points (``chip_smoke.py``, ``benchmarks/run.py`` and the example
mains) call ``enable()`` once, before their first compile. Importing
``repro`` never turns the cache on, so the test suite compiles as before.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no path
is set here. Otherwise the cache lives at ``<checkout>/.jax_cache``. The
path is fixed on purpose: a later run finds an entry only at the same path.

By default JAX does not cache a program that compiled in under a second.
One ``chip_smoke.py`` pass on a TPU v5e made 157 backend compiles, 140 of
them under a second (13.4 s together), so the threshold is lowered to zero
unless ``JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS`` is set.
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
MIN_TIME_VAR = "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get(ENV_VAR)
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    if MIN_TIME_VAR not in os.environ:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
