"""Where the entry points keep JAX's persistent compilation cache.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
nothing is configured here.  Otherwise every entry point (chip_smoke.py,
benchmarks/kernels_bench.py, launch/serve.py, launch/train.py) points
the cache at one fixed ``.jax_cache/`` inside the checkout — the path is
part of the cache key, so it must not move between runs.  Called from
``main``, never at import.
"""
from __future__ import annotations

import os

import jax

CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def use_compile_cache() -> None:
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
