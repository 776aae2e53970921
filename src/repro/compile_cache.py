"""JAX's persistent compilation cache for the programs' entry points.

`chip_smoke.py` and `benchmarks/run.py` call `enable_compile_cache()`
before their first compile; library code and tests never do, so tests
run with the cache off.  Where `JAX_COMPILATION_CACHE_DIR` is set, JAX
reads it itself and this module sets nothing.  Otherwise the cache lives
at one fixed path inside the checkout, `<repo>/.jax_cache` (gitignored):
the path is part of what a later process must find, so it is never a
temporary, pid- or time-derived directory.
"""

from __future__ import annotations

import os
import pathlib

import jax

REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
