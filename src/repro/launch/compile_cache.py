"""JAX's persistent compilation cache at one fixed place.

A cold ResNet50 serve compiles about fifty Mosaic conv kernels per serve
mode; the cache lets the next process on the same machine skip that.
The cache path is part of each entry's key, so it must never move
between runs.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/.jax_cache (git-ignored); this file is src/repro/launch/
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory: the one
    ``JAX_COMPILATION_CACHE_DIR`` names (JAX reads that variable itself,
    so nothing else is set), else ``DEFAULT_DIR``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
