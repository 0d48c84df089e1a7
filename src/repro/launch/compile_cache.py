"""JAX's persistent compile cache for the entry points.

Each entry point (``chip_smoke.py``, ``launch/reduce.py``,
``launch/reduce_server.py``) calls :func:`configure`
first thing in ``main``.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
already reads it and nothing is changed; otherwise the cache goes to one
fixed directory inside the checkout (listed in ``.gitignore``), so a second
run of the same program loads its executables instead of compiling them.
Importing :mod:`repro` never touches the cache: the tests run without it.
"""
from __future__ import annotations

import os
from pathlib import Path

# <checkout>/.jax_cache — fixed, because the path is part of the cache key.
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def configure() -> str:
    """Point the persistent compile cache at :data:`CACHE_DIR` unless
    ``JAX_COMPILATION_CACHE_DIR`` is set; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
