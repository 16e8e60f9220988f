"""Where JAX keeps its persistent compilation cache, decided in one place.

Every entry point (``launch.serve``, ``chip_smoke.py``, the benchmarks) calls
``enable_compile_cache()`` before its first compile:

* ``JAX_COMPILATION_CACHE_DIR`` set in the environment wins — JAX reads it
  itself and no other directory is set in code;
* otherwise the cache lives at one fixed path inside the checkout,
  ``<repo>/.jax_cache`` (listed in ``.gitignore``), so repeated runs of the
  same checkout hit it.  The path is part of the cache key, so it never
  depends on a temp dir, a pid or the time.
"""

from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
