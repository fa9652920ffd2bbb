"""Persistent XLA compilation cache for the command-line entry points.

A whole FL round of the paper CNN takes tens of seconds to compile for
a TPU, so entry points keep JAX's persistent cache on.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing
here overrides it; otherwise the cache goes to ``.jax_cache`` at the
root of the checkout.  The path is fixed because it is part of the
cache key: a directory that moves never hits.

Call :func:`enable_compile_cache` from a ``main`` before the first
compile, never at import, so library users and tests keep JAX's own
defaults.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on for every program this process
    compiles; returns the cache directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
