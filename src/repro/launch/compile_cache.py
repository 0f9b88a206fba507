"""Where JAX keeps its persistent compilation cache.

Called by entry points only (``chip_smoke.py``, ``repro.launch.train``,
``repro.launch.serve``), never when a ``repro`` module is imported.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/.jax_cache (git-ignored); the checkout is three levels above src/
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Return the cache directory in use. ``$JAX_COMPILATION_CACHE_DIR``, when
    set, is read by JAX itself and nothing else is set here. Otherwise the
    cache goes to the fixed ``DEFAULT_DIR``: a directory that moves between
    runs never hits."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
