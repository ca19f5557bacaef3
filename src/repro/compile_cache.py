"""JAX's persistent compilation cache, one fixed place per checkout.

Entry points call `enable()` before their first compile (never at import):
repeated runs then skip compiling the window-scan buckets, the eq.-13
search and the client update. `JAX_COMPILATION_CACHE_DIR`, when set, wins
and JAX reads it itself; otherwise the cache lives in `<repo>/.jax_cache/`.
The path is part of the cache key, so it never depends on a temp name, a
pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
