"""JAX's persistent compilation cache, at a place the next run finds again.

Every entry point (``chip_smoke.py``, ``repro.launch.train``,
``repro.launch.serve``) calls :func:`use_compile_cache` before it compiles
anything.  When ``JAX_COMPILATION_CACHE_DIR`` is set,
JAX reads that directory itself and nothing else is set here.  Otherwise
the cache lives at one fixed, git-ignored path inside the checkout: the
path is part of the cache key, so a temporary or per-process directory
would never hit.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent cache at its directory; returns the path."""
    given = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if given:
        return given
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
