"""JAX's persistent compilation cache for the entry points.

Every process compiles the chunk programs and kernels again unless JAX
finds them in its persistent cache.  The cache directory is part of the
key, so it stays at one fixed place: ``JAX_COMPILATION_CACHE_DIR`` when the
environment sets it (JAX reads the variable itself), otherwise
``.jax_cache/`` at the repository root (git-ignored).

Entry points (``chip_smoke.py``, the launchers) call ``enable()`` after
parsing their arguments; importing the library changes nothing.
"""
from __future__ import annotations

import os
import pathlib

import jax

REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
