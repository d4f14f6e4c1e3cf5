"""JAX's persistent compilation cache at a fixed path.

A cold process compiles every shape it meets, and at full width one prefill
program takes tens of seconds. The cache keeps those programs across
processes on one machine, but only if every process looks in the same
directory: a path built from a temporary name, a pid or the time never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["REPO_ROOT", "enable_compile_cache"]

#: the checkout this package was loaded from (``src/repro/launch/..``)
REPO_ROOT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses it and
    nothing else is set here. Otherwise the cache lives at
    ``<checkout>/.jax_cache`` (git-ignored).
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
