"""JAX's persistent compilation cache, placed by the entry points.

The cache directory is part of what makes an entry hit, so it must not
move between runs: no temporary name, pid or time goes into it.  Only the
entry points (``chip_smoke.py``, ``repro.launch.train``,
``benchmarks.run``) call :func:`use_compile_cache`; importing this module
sets nothing.
"""
from __future__ import annotations

import os
import pathlib

import jax

REPO_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here; otherwise the cache is ``<repo>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
