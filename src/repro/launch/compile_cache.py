"""JAX's persistent compilation cache, at one fixed place.

Call `enable_compile_cache()` before compiling anything. Where
`JAX_COMPILATION_CACHE_DIR` is set, JAX already reads it and nothing else
is configured. Otherwise the cache goes to `<checkout>/.jax_cache/` (git
ignores it): a fixed path, because the path is part of what a later run
must match to find its entries.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Returns the directory the compile cache uses."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
