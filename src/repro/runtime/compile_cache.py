"""JAX's persistent compilation cache, switched on by the entry points.

A cold process compiles every step program again; with the cache on, a
later process with the same programs reads them back. The directory is
fixed, so every run of one checkout shares it.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: `<checkout>/.jax_cache` (listed in .gitignore)
DEFAULT_CACHE_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory.

    Where `JAX_COMPILATION_CACHE_DIR` is set, JAX already keeps its cache
    there and nothing is changed. Otherwise the cache goes to
    `DEFAULT_CACHE_DIR`. Call it from a `main`, never at import: a
    library user decides for their own process."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
