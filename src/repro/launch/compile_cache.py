"""JAX's persistent compilation cache for the entry points.

`enable_compile_cache()` is called first thing by every program that
compiles for the device (`repro.launch.cocoa_train`, `chip_smoke.py`, the
`benchmarks` mains). Where `JAX_COMPILATION_CACHE_DIR` is set, JAX already
reads it and nothing else is set here. Otherwise the cache lives at the
fixed `<repo>/.jax_cache` (listed in `.gitignore`): the path is part of
what a later run has to find, so it is never built from a temporary
name, a process id or the time.
"""
from __future__ import annotations

import os
import pathlib

import jax

REPO_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
