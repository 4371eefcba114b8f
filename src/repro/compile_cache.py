"""JAX's persistent compilation cache for the repo's entry points.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, ``examples/``) call
``enable()`` once, before their first compile. Tests do not: they compile
small shapes, and compiles for a described (unattached) TPU cannot be read
back from a cache anyway.
"""

from __future__ import annotations

import os
import pathlib

import jax

# Fixed, never temporary: the cache directory is part of what a later run
# must find again.
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the cache: JAX reads it
    itself and nothing else is set here. Otherwise the cache is the fixed
    ``<repo>/.jax_cache`` (git-ignored).
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
