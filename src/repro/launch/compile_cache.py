"""Where JAX keeps its persistent compilation cache.

The entry points call ``enable_compile_cache`` from ``main``, never at
import. A directory given from outside in ``JAX_COMPILATION_CACHE_DIR``
wins: JAX reads that variable itself, so no path is set here. Otherwise
the cache lives at ``<checkout>/.jax_cache`` (git-ignored). The path is
fixed -- it is part of the cache's key, so a path derived from a temp
name, a process id or the time would never hit.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
