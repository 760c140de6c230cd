"""Persistent compilation cache location, set once at program start.

A TPU call starts with no compiled code unless the cache directory holds
programs from an earlier run, and the path is part of the cache key, so
it must not move between runs.  When ``JAX_COMPILATION_CACHE_DIR`` is set
JAX reads it itself and nothing here overrides it; otherwise the cache
goes to ``<root>/.jax_cache`` (listed in ``.gitignore``).
"""

from __future__ import annotations

import os

import jax

__all__ = ["use_compile_cache"]


def use_compile_cache(root) -> str:
    """Enable the persistent cache; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(os.path.abspath(root), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
