"""Where JAX keeps its persistent compilation cache.

The entry points call :func:`enable_compile_cache` before their first
compile; importing this module changes nothing. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is
set here. Otherwise the cache lives in ``.jax_cache/`` at the repository
root: a fixed path, so a later run of the same programs finds it.
"""

from __future__ import annotations

import os
from pathlib import Path

CACHE_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent cache at its directory; returns the path."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
