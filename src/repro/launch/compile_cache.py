"""JAX's persistent compilation cache for every entry point.

The serve CLI, the train CLI and ``chip_smoke.py`` call
:func:`enable_compile_cache` before their first compile, so a process that
compiles the same step as an earlier one on the same machine loads it from
disk instead of compiling it again (a full-width 24-layer step takes seconds
to compile).

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and that
directory stands. Otherwise the cache lives at a fixed directory inside the
checkout (``<repo>/.jax_cache``, listed in ``.gitignore``): the path is part
of what the cache is found by, so it is never named after a temporary
directory, a pid or the time.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    if os.environ.get(ENV):
        return jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
