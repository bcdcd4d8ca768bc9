"""JAX's persistent compilation cache, set up in one place.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, the examples) call
``enable()`` before their first JAX computation. Where the environment
sets ``JAX_COMPILATION_CACHE_DIR``, JAX keeps its cache there and this
module sets no other directory. Otherwise the cache lives at
``<repo>/.jax_cache``: a fixed path, because the directory is part of
what a later run must find again. Every program is cached, however short
its compile: each Pallas kernel compiles in under a second, under JAX's
default threshold.
"""

from __future__ import annotations

import os
import pathlib

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on; returns its directory."""
    import jax

    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
