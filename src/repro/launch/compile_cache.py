"""Where JAX keeps compiled programs between runs.

Entry points call :func:`enable_compile_cache` from ``main()``; importing
this module sets nothing.
"""
from __future__ import annotations

import os
import pathlib

import jax

#: the checkout's own cache directory (``src/repro/launch`` -> checkout)
CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its path.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    this sets no other directory.  Otherwise the cache goes to the fixed
    ``.jax_cache/`` under the checkout: a fixed path is part of every
    cache key, so a directory named per run would never hit."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
