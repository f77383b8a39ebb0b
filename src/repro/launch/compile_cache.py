"""Persistent JAX compilation cache for the entry points.

Every entry point (``chip_smoke.py``, ``launch/serve.py``,
``benchmarks/run.py``) calls :func:`enable_compile_cache` before it
compiles anything.  The rule:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it, so the helper
  uses that directory and sets nothing else;
* unset: the cache goes to ``<checkout>/.jax_cache`` (gitignored).  The
  path is fixed, never a temp, pid or time-based one, because the
  directory is part of what makes a later run find the entries again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
