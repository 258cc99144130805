"""JAX's persistent compilation cache for the entry points.

Entry points (``chip_smoke.py``, ``repro.launch.serve``) call
:func:`enable_compile_cache` once, before their first compile; importing
the package never does.  The directory is ``JAX_COMPILATION_CACHE_DIR``
where that is set, and otherwise a fixed path inside the checkout (listed
in ``.gitignore``).  The path is part of the cache's key, so it is never
built from a temporary name, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
#: the checkout's own cache directory (repo root / .jax_cache)
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir() -> str:
    """The one directory the persistent compilation cache uses."""
    return os.environ.get(ENV) or str(DEFAULT_DIR)


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at
    :func:`compile_cache_dir` and return that path."""
    path = compile_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
