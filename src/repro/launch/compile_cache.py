"""Persistent XLA compilation cache at one fixed place.

The cache key includes the directory, so a run only finds what an
earlier run compiled if both use the same path. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it by itself and nothing
is changed here; otherwise the cache lives in ``<repo>/.jax_cache``.
Call :func:`enable` at the start of an entry point, never at import.
"""

from __future__ import annotations

import os
import pathlib

import jax

#: ``<repo>/.jax_cache``: this file is ``<repo>/src/repro/launch/*.py``
CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> None:
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
