"""JAX's persistent compilation cache, placed from outside the program.

An arena program of a full-size deployment takes minutes to compile, so
entry points that run one (``chip_smoke.py``, ``benchmarks/run.py``) call
``enable_compile_cache()`` first.  Nothing in ``repro`` does this at
import: a library must not choose where its users' compiled code lives.
"""
from __future__ import annotations

import os
from pathlib import Path

# the root of the checkout: a fixed path, because the directory is part of
# what a later run has to find again
REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    this sets no other directory.  Otherwise the cache goes to
    ``.jax_cache`` at the root of the checkout."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    return path
