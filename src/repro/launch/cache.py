"""JAX's persistent compilation cache for the command-line entry points.

A cold full-width prefill or decode step compiles for tens of seconds; the
cache lets a second run of the same command load the executable instead.
The cache directory is part of what a cached entry is found by, so it is a
fixed path: ``JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads it
itself), else ``<checkout>/.jax_cache``.  Library code and tests never turn
the cache on; only the command-line entry points (``chip_smoke.py`` and
``python -m repro.launch.*``) call ``enable_compile_cache``.  The dry-run
does not: a cache hit would skip the compile whose HLO dump it reads.
"""
from __future__ import annotations

import os

import jax

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                         "..", "..", ".."))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(REPO_ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
