"""JAX's persistent compilation cache, placed for the repo's entry points.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``,
``examples/flight_delay_pipeline.py``) call :func:`enable_compile_cache`
once, before their first compile.  Library modules and tests never call
it.
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# a fixed path: the cache directory is part of what a later run must find
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on the persistent cache and return its directory.

    ``$JAX_COMPILATION_CACHE_DIR`` wins when set (JAX reads it itself);
    otherwise the cache lives at ``<checkout>/.jax_cache``.  Every program
    is kept, however quick its compile: the batched AEAD fast path
    compiles many small per-shape programs that would each fall under
    JAX's default one-second threshold.
    """
    path = os.environ.get(ENV_VAR) or DEFAULT_DIR
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
