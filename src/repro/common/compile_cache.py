"""JAX's persistent compilation cache for the repo's entry points.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, ``examples/``)
call ``enable_compile_cache()`` once, before their first compile.
Importing ``repro`` never turns the cache on: a library import should
not start writing files, and ahead-of-time compiles for a described
chip (tests/test_chip_compile.py) write entries no later run can read.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it and
keeps its cache there; nothing here overrides it.  Otherwise the cache
goes to the fixed ``<repo>/.jax_cache`` (git-ignored).  The directory is
part of what makes a later run find an entry, so it is never derived
from a temporary name, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory
    (the environment's ``JAX_COMPILATION_CACHE_DIR`` when set, else
    ``<repo>/.jax_cache``)."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
