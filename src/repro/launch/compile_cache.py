"""JAX's persistent compilation cache, placed once per entry point.

A cold serving run compiles a whole-model step program; the persistent
cache lets a later process load it instead.  Entry points
(``chip_smoke.py``, ``repro.launch.serve.main``, ``benchmarks/run.py``)
call :func:`enable` when they start, never at import.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads the cache
from there and nothing is changed.  Otherwise the cache goes to the
fixed ``<repo>/.jax_cache``: the directory is part of every entry's key,
so a path that moved between runs (a temp dir, a pid) would never hit.

The cache key hashes each Pallas kernel's serialized Mosaic body, and
that body embeds the source locations of its lowering — by default the
whole Python call stack.  A step lowered from another call path (another
script, a benchmark, a retry) would then never hit, so locations are cut
to the innermost user frame.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
