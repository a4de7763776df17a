"""JAX's persistent compilation cache, placed for the repo's entry points.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``) call
:func:`enable_compile_cache` once before compiling anything; importing this
module changes nothing, and tests never call it.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: where the cache lives when the environment names no directory: a fixed
#: path inside the checkout (the path is part of what the cache is found
#: by, so it is never built from a temporary name, a pid or the time)
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself, so
    no directory is set here.  Otherwise the cache goes to
    :data:`DEFAULT_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
