"""JAX's persistent compilation cache, kept at one fixed path.

Entry points call `enable_compile_cache()` before their first compile so
that a second run in the same checkout loads its programs instead of
compiling them again.  That only works if the path never moves between
runs: no temporary name, pid or time in it.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/.jax_cache (this file is src/repro/launch/compile_cache.py)
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> None:
    """Point JAX's compilation cache at `CACHE_DIR`, unless
    JAX_COMPILATION_CACHE_DIR is set: JAX reads that itself, and then no
    other path is configured here."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
