"""Where JAX keeps its persistent compilation cache for this repo's entry points.

A cold process on a chip compiles every program again, and whole-state
update and read programs take tens of seconds each.  The persistent cache
keeps them across processes: in ``JAX_COMPILATION_CACHE_DIR`` when that is
set, otherwise in a fixed ``<repo>/.jax_cache`` (the directory is part of
the cache key, so it must not move between runs).  Entry points call
:func:`enable_compile_cache` once, before their first compile; nothing
else in the repo sets a cache.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_ROOT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = REPO_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
