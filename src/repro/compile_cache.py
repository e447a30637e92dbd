"""JAX's persistent compilation cache, placed from outside the program.

Entry points call ``enable_compile_cache()`` at the start of ``main()``,
never at import.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads
it on its own and this module sets no other directory.  Otherwise the
cache lives in ``.jax_cache/`` at the root of the source checkout: a
fixed path, so a later run finds what an earlier one compiled (the path
is part of the cache's key, so a directory that moves never hits).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its
    directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
