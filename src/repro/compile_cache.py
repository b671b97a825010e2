"""JAX's persistent compilation cache for the repo's entry-point scripts.

Called by `chip_smoke.py` and the scripts under `benchmarks/` and
`examples/` before their first compile — never on import of the library
and never from the tests.  Where `JAX_COMPILATION_CACHE_DIR` is set, JAX
reads it itself and nothing is set here.  Otherwise the cache lives at a
fixed directory inside the checkout (git-ignored): the path is part of
what the cache is keyed on, so it must not move between runs.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
