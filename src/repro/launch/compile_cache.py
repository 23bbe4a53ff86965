"""Persistent XLA compilation cache for the entry points.

Called by ``launch/train.py:main``, ``benchmarks/run.py`` and
``chip_smoke.py`` before their first compile, never on import of a library
module.  The cache key includes the directory, so it lives at one fixed
path: ``$JAX_COMPILATION_CACHE_DIR`` when set (JAX reads that variable
itself), otherwise ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
