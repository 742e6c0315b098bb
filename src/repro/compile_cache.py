"""Persistent XLA compilation cache, placed from outside the program.

Scripts that drive the chip call :func:`enable_compile_cache` once at start
(never at library import).  The directory is ``JAX_COMPILATION_CACHE_DIR``
when that is set — JAX reads the variable itself, so no other directory is
set in code — and otherwise a fixed ``.jax_cache/`` inside the checkout
(git-ignored).  The path is part of the cache key, so it is never built from
a temporary name, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache(root: str | os.PathLike) -> str:
    """Turn the persistent cache on; returns the directory in use.

    ``root`` is the checkout the fallback ``.jax_cache/`` lives in.
    """
    path = os.environ.get(ENV)
    if not path:
        path = str(Path(root).resolve() / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
