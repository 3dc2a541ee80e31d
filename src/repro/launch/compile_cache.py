"""JAX's persistent compilation cache, shared by the launchers and
``chip_smoke.py``.

A cold process recompiles every program (the 28-layer serving step of
qwen3-1.7b among them); the cache keeps compiled programs across processes.
Importing ``repro`` does not turn it on: an entry point calls
:func:`enable_compile_cache` first thing.
"""
from __future__ import annotations

import os
import pathlib

import jax

# src/repro/launch/compile_cache.py -> the checkout's root
CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as JAX reads it, and
    nothing else is set here.  Otherwise the cache lives at ``.jax_cache/``
    in the checkout: a fixed path, because the path is part of what the
    cache keys on."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
