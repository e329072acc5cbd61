"""Where JAX's persistent compilation cache lives.

An 8B serving program takes tens of seconds to compile, and a chip-tool
call or a restarted server starts with nothing compiled. The cache
directory is part of the cache key, so it must not move between runs:
never a temporary name, a pid or a time.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
#: the fixed default: `<checkout>/.jax_cache` (listed in .gitignore)
DEFAULT_DIR = Path(__file__).resolve().parent.parent.parent / ".jax_cache"


def place_compile_cache() -> str:
    """Point JAX at its persistent compile cache; returns the directory.

    If JAX_COMPILATION_CACHE_DIR is set, JAX reads it itself and this
    sets nothing, so the cache is there and nowhere else. Otherwise the
    cache goes to the one fixed path under the checkout. Every entry
    point that compiles calls this once, before its first compile.
    """
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
