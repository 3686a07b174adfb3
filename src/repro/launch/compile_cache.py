"""JAX's persistent compilation cache for the entry points.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, ``launch.serve``,
``launch.train``) call :func:`enable_compile_cache` at the start of
``main`` — never at import, so importing a module changes no JAX state.
"""
from __future__ import annotations

import os
import pathlib

#: Cache directory when none is given from outside: fixed inside the
#: checkout, because the path is part of the cache key (a directory that
#: moves between runs never hits).  Listed in ``.gitignore``.
CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting: JAX
    already reads it, and nothing is set here.  Otherwise the cache goes to
    :data:`CHECKOUT_CACHE_DIR`.
    """
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
