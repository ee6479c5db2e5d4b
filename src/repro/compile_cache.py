"""Persistent XLA compile cache for the command-line entry points.

Every process that drives the chip compiles the same few programs (the
training scan, the serving engines), so entry points keep compiled
executables on disk and a second run loads them instead of compiling.
Tests never call this: a compile for a described-but-absent chip cannot be
read back, and the suite must not depend on what a previous run left.
"""
from __future__ import annotations

import os
import pathlib

import jax

# fixed, inside the checkout (and gitignored): the cache key includes the
# directory, so a path that moved between runs would never hit
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compile cache at a stable directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and
    left alone; otherwise the cache goes to ``<repo>/.jax_cache``.
    Returns the directory in use.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
