"""JAX's persistent compilation cache for the serving entry points.

A full-width serving run compiles a prefill, a decode step and a mixed
step of the whole model before it serves a token; the persistent cache
lets the next process on the same machine load them instead.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: fixed directory inside the checkout (listed in .gitignore). The path is
#: part of what JAX keys the cache on, so it never depends on a temporary
#: name, a process id or the time.
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps the cache
    there and nothing is set in code."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
