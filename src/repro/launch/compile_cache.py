"""Persistent XLA compile cache for the entry points (launchers and
`chip_smoke.py`) — called from their `main`, never at library import.

A 22-layer program takes minutes to compile cold; the cache lets the
next process on the same tree load it instead.  Where
`JAX_COMPILATION_CACHE_DIR` is set, JAX already reads it and nothing
else is set here.  Otherwise the cache lives at one fixed path inside
the checkout (`.jax_compile_cache/`, git-ignored): a path built from a
temporary name, a pid or the time would never be found again.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: <checkout>/.jax_compile_cache  (this file is src/repro/launch/…)
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_compile_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
