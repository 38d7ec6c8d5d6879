"""Placement of JAX's persistent compilation cache.

A process that runs on the chip calls ``enable_compile_cache()`` once, before
its first compile. ``JAX_COMPILATION_CACHE_DIR``, when set, places the cache
(JAX reads that variable itself, and nothing here overrides it). Otherwise
the cache lives at a fixed directory inside the checkout, so every run from
the same checkout finds the entries of the last one: the path is part of the
cache key, so it is never a temporary name, a process id or a time. Library
code and tests never call this.
"""
from __future__ import annotations

import os
import pathlib

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
