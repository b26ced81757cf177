"""JAX's persistent compilation cache for the programs that run on a chip.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets nothing. Otherwise the cache goes to ``.jax_cache/`` at the
root of the checkout: a fixed path, because the path is part of what a
cached entry is found by, and a directory named after a temp dir, a pid or
a time would never be hit again. Tests do not call this; their cache stays
off.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
