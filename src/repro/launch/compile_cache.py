"""Persistent XLA compilation cache for the entry points.

Called from the ``main()`` of each entry point, never at import: tests and
library callers keep JAX's default (no persistent cache).
"""
from __future__ import annotations

import os
from pathlib import Path

# <checkout>/.jax_cache — a fixed path, because the cache key includes it
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and is
    left alone; otherwise the cache lives in ``<checkout>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
