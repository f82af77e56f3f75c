"""Where JAX keeps its persistent compilation cache.

The cache's directory is part of its key: a directory that moves between
runs never hits.  So the entry points (launch/serve.py, launch/train.py,
chip_smoke.py) place it in one fixed spot unless the environment already
chose one.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: <checkout>/.jax_cache (listed in .gitignore).
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    With JAX_COMPILATION_CACHE_DIR set, JAX reads it itself and nothing is
    set here.  Otherwise the cache goes to CHECKOUT_CACHE_DIR."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
