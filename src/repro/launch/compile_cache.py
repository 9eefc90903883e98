"""JAX's persistent compilation cache, placed from outside or at a fixed path.

Entry points (``launch/serve.py``, ``chip_smoke.py``,
``benchmarks/run.py``) call :func:`enable_compile_cache` once, before
their first compile; importing this module changes nothing.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads the variable itself, and
  nothing is set in code.
* unset: the cache goes to ``<repo>/.jax_cache`` (gitignored).  The path
  is fixed — never derived from a temporary name, a process id or the
  time — because it is part of the cache key: a directory that moves
  never hits.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
