"""Placement of JAX's persistent compilation cache for the entry points.

JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself: when it is set, nothing
here touches the configuration. Otherwise the cache goes to one fixed
directory, ``.jax_cache/`` at the root of the checkout (git-ignored).
The directory is part of what lets a later run find its entries, so it
never depends on a temporary directory, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

#: The cache directory used when ``JAX_COMPILATION_CACHE_DIR`` is unset.
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; return the directory it uses."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
