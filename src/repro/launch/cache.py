"""JAX's persistent compilation cache for the entry points.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
here changes it. Otherwise the cache lives at ``<checkout>/.jax_cache``: a
fixed path, because the path is part of what a cache entry is found by.
Call :func:`use_compile_cache` from an entry point's ``main()`` before its
first JAX operation, never at import.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def use_compile_cache() -> None:
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    jax.config.update("jax_compilation_cache_dir",
                      str(CHECKOUT / ".jax_cache"))
