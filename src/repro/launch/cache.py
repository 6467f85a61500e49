"""Persistent XLA compilation cache for the command-line entry points."""
from __future__ import annotations

import os
from pathlib import Path

import jax

# The checkout root (``src/repro/launch/cache.py`` -> three levels up).  A
# fixed path: the cache directory is part of what a later run has to find.
_DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    other directory is set here.  Otherwise the cache goes to
    ``<checkout>/.jax_cache``.  Entry points call this first, under their
    ``__main__`` guard, so importing or calling them from tests leaves the
    cache off.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(_DEFAULT_DIR))
    return str(_DEFAULT_DIR)
