"""JAX's persistent compilation cache, placeable from outside.

A serving run compiles a whole decode step and every Pallas kernel before
its first token; the persistent cache lets the next process in the same
place skip that. The directory is part of what makes a cache hit, so it is
never derived from a temp dir, a PID or a clock.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: fixed in-checkout default (``src/repro/launch/`` sits three levels down)
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; call before the first compile.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already reads it and no
    other directory is set here. Otherwise the cache goes to
    :data:`DEFAULT_DIR`. Returns the directory in use.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
