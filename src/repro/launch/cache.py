"""JAX's persistent compilation cache, kept in one fixed directory.

The directory is ``$JAX_COMPILATION_CACHE_DIR`` where that is set, and
``<checkout>/.jax_cache`` (git-ignored) otherwise.  The path is never
derived from a temporary name, a process id or the time: a later run
finds its programs again only at the same path.  Entry points call
:func:`enable_compile_cache` once, before their first compile; importing
this module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    path = os.environ.get(ENV_VAR) or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
