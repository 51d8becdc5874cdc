"""Persistent JAX compilation cache placed from outside the program.

A fresh process on a chip compiles every jitted step again; a persistent
cache lets later processes (and later runs on the same machine) skip it.
The cache is keyed on its path, so the path must not move between runs.
"""

from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT_ROOT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_CACHE_DIR = CHECKOUT_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its path.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here.  Otherwise the cache lives at the fixed
    ``.jax_cache/`` under the checkout root (listed in ``.gitignore``)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
