"""Where JAX keeps its persistent compilation cache.

Entry points call `use_compile_cache()` first thing in `main()`; nothing
calls it at import.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout's own cache directory (git-ignored).  A fixed path: the
#: cache key includes it, so a directory that moves never hits.
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and this
    sets no other directory.  Otherwise the cache goes to `DEFAULT_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
