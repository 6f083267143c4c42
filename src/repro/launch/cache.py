"""JAX's persistent compilation cache, placed once per process.

Entry points call `enable_compile_cache` before their first compile; tests
and library code never do, so importing the package leaves the cache off.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it by itself and
    this sets no other directory.  Otherwise the cache goes to
    ``.jax_cache/`` at the root of the checkout: a fixed path, so a later
    run from the same checkout finds what an earlier one compiled.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
