"""JAX's persistent compilation cache for the entry points.

Called by the drivers (``launch/train.py``, ``launch/serve.py``,
``benchmarks/run.py``, ``chip_smoke.py``), never on import of the library.
"""
from __future__ import annotations

import os
import pathlib

import jax

# <checkout>/.jax_cache (listed in .gitignore). The path is part of the
# cache key, so it is fixed: never a temp dir, pid or time.
_DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and no
    other directory is set here."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(_DEFAULT_DIR))
    return str(_DEFAULT_DIR)
