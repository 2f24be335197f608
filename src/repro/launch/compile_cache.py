"""Where JAX keeps its persistent compilation cache.

Entry points call ``configure_compile_cache()`` first thing in ``main()``;
importing this module changes nothing.  ``JAX_COMPILATION_CACHE_DIR``, when
set, names the directory and nothing else is set.  Otherwise the cache goes
to ``<repo>/.jax_cache``: a fixed path, because the path is part of what a
later process must match to find its entries (never a temporary name, a
process id or the time).
"""
from __future__ import annotations

import os
import pathlib

import jax

#: the checkout's root: this file is <repo>/src/repro/launch/compile_cache.py
REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; return it."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        REPO_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
