"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``repro.launch.train``,
``repro.launch.serve``, ``benchmarks.common``) call
:func:`enable_compile_cache` before they compile anything, and no other
code in the repository sets a cache directory.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads the variable itself and
  that directory is used; nothing is set in code.
* unset: the cache goes to ``<checkout>/.jax_cache`` (git-ignored). The
  path is fixed, because the directory is part of what a later process
  must find again: a path named after a pid, a timestamp or a temporary
  directory would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory
    (the environment's, else the checkout's fixed ``.jax_cache``)."""
    from_env = os.environ.get(ENV_VAR)
    if from_env:
        return from_env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
