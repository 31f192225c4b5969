"""JAX persistent compilation cache for the repo's entry points.

Scripts call `enable_compile_cache(<checkout root>)` before their first
compile; importing `repro` never touches the cache.  Where
`JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and nothing else is
configured.  Otherwise the cache is `<checkout>/.jax_cache`: a fixed path,
because the path is part of every cache key, so a temporary or per-process
directory would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CACHE_ENV", "enable_compile_cache"]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache(checkout: str | os.PathLike) -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    path = str(Path(checkout).resolve() / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
