"""Where compiled programs persist between runs.

The key of JAX's persistent compilation cache includes the cache path,
so a directory that moves (a temporary name, a process id) never hits.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# a fixed directory inside the checkout (src/repro/launch -> repo root),
# listed in .gitignore
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_compile_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here; otherwise the cache goes to :data:`CACHE_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)


__all__ = ["CACHE_DIR", "enable_compile_cache"]
