"""Persistent XLA compilation cache, placed from outside.

Every entry point calls ``enable_compile_cache()`` before its first compile.
``JAX_COMPILATION_CACHE_DIR``, when set, names the directory and nothing
else is set here. Otherwise the cache lives at the fixed ``<checkout>/.jax_cache``
(the path is part of what a later run must find again, so it never depends
on a temp name, a PID or the time), and the variable is exported so that
worker processes spawned afterwards share the same directory.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns it."""
    path = os.environ.get(ENV)
    if not path:
        path = str(DEFAULT_DIR)
        os.environ[ENV] = path
    jax.config.update("jax_compilation_cache_dir", path)
    return path
