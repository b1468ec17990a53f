"""JAX's persistent compilation cache, kept at one fixed place.

A cold start of the full-width engine compiles one prefill program per
shape bucket and one decode-window program per batch bucket; the
persistent cache lets the next process on the same machine skip them.
The directory is part of what makes a cache hit, so it never contains a
temporary name, a pid or a time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: ``<repo>/.jax_cache`` (gitignored)
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    this sets no directory of its own; otherwise the cache goes to
    :data:`DEFAULT_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
