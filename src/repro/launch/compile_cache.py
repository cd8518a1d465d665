"""Persistent compilation cache for the chip entry points.

A cold run compiles every program, a 32-layer scan among them.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing here
overrides it.  Otherwise the cache lives at the fixed ``<checkout>/.jax_cache``
(listed in ``.gitignore``): the path is part of the cache key, so it must not
depend on a temp directory, the pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Point JAX's persistent cache at its directory; -> that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
