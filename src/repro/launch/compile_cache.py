"""Persistent JAX compilation cache for the entry points.

Entry points (``chip_smoke.py``, ``launch/serve.py``, ``benchmarks/run.py``)
call ``enable_compile_cache()`` first, before anything compiles. Importing
this module sets nothing.
"""
from __future__ import annotations

import os

import jax

# src/repro/launch/compile_cache.py -> the checkout root
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is set. Otherwise the cache lives at the fixed path
    ``<repo>/.jax_cache``: the path is part of the cache key, so a
    directory built from a tempdir, pid or time would never hit."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(REPO_ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
