"""JAX's persistent compilation cache, placed from outside the program.

Entry points call :func:`enable_compile_cache` before their first compile.
Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it and this module
sets nothing.  Otherwise the cache lives at a fixed ``.jax_cache/`` in the
repository root: the directory is part of what a later run must find again,
so it is never built from a temporary name, a process id or the time.
"""

from __future__ import annotations

import os
import pathlib

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    import jax
    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
