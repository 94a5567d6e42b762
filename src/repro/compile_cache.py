"""JAX's persistent compilation cache, kept at one fixed place.

The cache key includes the directory, so a cache that moves never hits.
``JAX_COMPILATION_CACHE_DIR`` wins when it is set (JAX reads it itself);
otherwise the cache lives in the checkout's ``experiments/jax_cache/``.
Entry points call :func:`enable_compile_cache` once at start-up; library
code and tests never do.
"""
from __future__ import annotations

import os

import jax

__all__ = ["DEFAULT_DIR", "enable_compile_cache"]

DEFAULT_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "experiments", "jax_cache"))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
