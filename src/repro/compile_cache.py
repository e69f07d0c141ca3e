"""JAX's persistent compilation cache, placed from outside the program.

``JAX_COMPILATION_CACHE_DIR``, when set, names the cache directory, and JAX
reads it by itself. Otherwise the cache lives in ``.jax_cache/`` at the
repository root: a fixed path, because the path is part of what a later
process must find again. Every compilation is cached, however quick: the
eager executor compiles many small programs. Entry points call
:func:`enable_compile_cache` once at start-up, before their first
compilation.
"""

from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; return the directory it writes to."""
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
