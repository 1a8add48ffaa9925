"""JAX's persistent compilation cache at a fixed place.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX keeps its cache there by
itself and nothing is set here.  Otherwise the cache goes to
``<repo>/.jax_cache``: a fixed path, so that every process started from
this checkout finds what an earlier one compiled.
"""

from __future__ import annotations

import os
import pathlib

import jax

CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
