"""Persistent XLA compilation cache, at one placeable path.

The dedup-pipeline programs (CDC scan, batched BLAKE3, the shard-mapped
manifest) are large graphs: one manifest program takes about a minute to
compile for a v5e, and the engine compiles one per (row length, row
count) bucket.  A persistent cache makes every process after the first
start warm.

The directory is part of the cache's key, so it must not move between
runs.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself
and this module sets no directory; otherwise the cache lives at one fixed
path inside the checkout (``<repo>/.jax_cache``, git-ignored).
"""

from __future__ import annotations

import os
from pathlib import Path

REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compilation_cache() -> Path:
    """Turn the persistent cache on; returns the directory in use."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        path = Path(env)
    else:
        path = REPO_CACHE_DIR
        path.mkdir(parents=True, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    return path
