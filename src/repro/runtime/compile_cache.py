"""Where JAX keeps its persistent compilation cache.

Call ``use_compile_cache()`` first thing in an entry point's ``main`` —
never at import.  When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already
reads it and the cache stays there.  Otherwise the cache goes to the fixed
``<repo>/.jax_cache``: the directory is part of every entry's key, so a
path that moved between runs (a temporary name, a pid, a time stamp)
would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_ROOT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = REPO_ROOT / '.jax_cache'


def use_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    if not os.environ.get('JAX_COMPILATION_CACHE_DIR'):
        jax.config.update('jax_compilation_cache_dir', str(DEFAULT_DIR))
    return jax.config.jax_compilation_cache_dir
