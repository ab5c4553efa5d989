"""JAX's persistent compilation cache at a fixed place.

Entry points (``python -m repro``, its figure runner ``benchmarks/run.py``
and ``chip_smoke.py``) call :func:`enable` before their first compile.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and this module
sets no directory.  Otherwise the cache lives at ``<checkout>/.jax_cache``
(listed in ``.gitignore``): a path with no temp name, pid or time in it,
because the path is part of what a later process must find again.
"""
from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def enable() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    import jax
    # cache every program: a replay kernel compiles in under the default
    # one-second threshold, and each one recurs in every run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_DIR)
    return CHECKOUT_DIR
