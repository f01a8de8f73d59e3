"""Persistent XLA compilation cache for the repo's entry points.

Entry points (``chip_smoke.py``, ``launch.train``, ``launch.serve``,
``benchmarks.run``) call :func:`enable` once before compiling anything;
library modules never do, so importing them changes no JAX setting.
"""

from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["enable", "CHECKOUT_CACHE_DIR"]

# a fixed path inside the checkout (git-ignored): a cache directory that
# moves between runs never hits
CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is changed here.  Otherwise the cache lives in ``.jax_cache/``
    at the root of the checkout.  Every compilation is cached, however
    short, so a second run in the same checkout compiles nothing anew.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return str(CHECKOUT_CACHE_DIR)
