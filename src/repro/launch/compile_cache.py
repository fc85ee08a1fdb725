"""Persistent JAX compilation cache at a path chosen from outside.

``JAX_COMPILATION_CACHE_DIR``, when set, names the cache and JAX reads it
itself; nothing else is set in code.  Otherwise the cache lives at a
fixed path inside the checkout (``.jax_cache``, git-ignored), never one
built from a temporary name, a process id or the time, so that the next
run in the same checkout finds what this one compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on before the first compile; returns
    the directory in use."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
