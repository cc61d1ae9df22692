"""Persistent XLA compilation cache placement for the entry points.

A compiled program is cached on disk keyed partly by the cache's path, so
the path must not move between runs. :func:`use_compile_cache` leaves an
externally set ``JAX_COMPILATION_CACHE_DIR`` to JAX (which reads it at
start-up) and otherwise pins the cache to ``<repo root>/.jax_cache``
(gitignored). Call it before the first compilation.
"""
from __future__ import annotations

import os
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = REPO_ROOT / ".jax_cache"


def use_compile_cache() -> str:
    """Returns the directory the persistent compilation cache writes to."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
