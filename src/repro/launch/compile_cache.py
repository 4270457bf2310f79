"""JAX's persistent compilation cache, placed from outside the program.

Entry points (``chip_smoke.py``, the examples, the throughput and serving
benchmarks) call :func:`enable_compile_cache` first thing in ``main``,
never on import.  Benchmarks that time compilation itself (synthesis time,
critical path, resource sweep, heatmap, roofline, ``benchmarks.run``) do
not: a warm cache would turn their compile times into cache reads.
"""

from __future__ import annotations

import os

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
# <repo>/.jax_cache: a fixed path, because the path is part of what a later
# run must find again (gitignored)
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on the persistent cache and return its directory.

    Where ``$JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is set here; otherwise the cache lives at ``<repo>/
    .jax_cache``.
    """
    path = os.environ.get(ENV)
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
