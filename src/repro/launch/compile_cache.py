"""JAX's persistent compilation cache, kept at a fixed place.

The cache directory is part of every entry's key, so a directory that
moves between runs (a temporary name, a pid, a time) never hits.
``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and
nothing here overrides it.  Otherwise the cache lives in ``.jax_cache``
at the root of the checkout, which ``.gitignore`` lists.

Every entry point calls :func:`use_compile_cache` before its first
compile: the launchers, ``python -m repro`` and ``chip_smoke.py``.
"""
from __future__ import annotations

import os
import pathlib

import jax

CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a fixed directory and
    return the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
