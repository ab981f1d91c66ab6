"""Where JAX's persistent compilation cache lives.

A program's first call compiles, which at real widths costs seconds to
minutes per program; the persistent cache lets the next process that
builds the same program read it back instead.  Its directory is part of
the cache's key, so it must not move between runs.  Every entry point
(``rescalk_run``, ``serve``, ``chip_smoke.py``) calls
``enable_compile_cache`` first thing; tests never do.
"""
from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache (this file is <checkout>/src/repro/launch/...)
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    no other directory is set here; otherwise the cache goes to the fixed
    ``<checkout>/.jax_cache``."""
    path = os.environ.get(ENV_VAR)
    if path:
        return path
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
