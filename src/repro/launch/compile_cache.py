"""JAX's persistent compilation cache: one rule for every entry point.

The serve and train launchers, ``chip_smoke.py`` and the benchmarks all
call ``enable_compile_cache`` before their first compile:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX already keeps its cache there,
  and no other directory is set in code.
* unset: the cache lives at the fixed ``<repo>/.jax_cache`` (gitignored).
  Never a temp, pid- or time-derived path: the directory is part of what
  a later run must find again, and a path that moves never hits.

Tests do not call it, so a test run writes no cache.
"""
from __future__ import annotations

import os

import jax

REPO_ROOT = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", ".."))
DEFAULT_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
