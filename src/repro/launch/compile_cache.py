"""JAX persistent compilation cache, placed from outside the program.

A cold run of a 32-layer model compiles its prefill, decode and DSA
programs from scratch; the persistent cache lets a later process on the
same machine load them instead.  The cache key includes the directory, so
the directory must not move between runs: no temporary name, pid or time.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, and this helper
  sets nothing.
* unset: the cache lives at ``<checkout>/.jax_cache`` (gitignored).

Call ``enable_compile_cache()`` at the top of an entry point, before the
first compile.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# src/repro/launch/compile_cache.py -> the checkout root
REPO_ROOT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = REPO_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
