"""JAX's persistent compilation cache for the repo's entry points.

`chip_smoke.py`, `benchmarks/run.py` and the `examples/` scripts call
`enable()` before their first compile; importing the package turns nothing
on.  Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it on its own and
this module sets no directory.  Otherwise the cache lives at a fixed
`<repo>/.jax_cache` (listed in `.gitignore`): the cache key includes the
directory, so a path that moved between runs would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
