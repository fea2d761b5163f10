"""Persistent XLA compilation cache for the program's entry points.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it and keeps its
cache there; nothing is set in code.  Otherwise the cache goes to one fixed
directory inside the checkout, ``<repo>/.jax_cache/`` (listed in
``.gitignore``).  The path is part of the cache key, so it is never built
from a temporary name, a process id or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns
    the directory in use.  Call first in an entry point's ``main``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
