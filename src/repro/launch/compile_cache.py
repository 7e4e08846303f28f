"""Persistent XLA compilation cache shared by the entry points.

``enable()`` is called once at the start of every entry point (the
serving and training launchers, the benchmark runner, ``chip_smoke.py``)
and never at import.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
reads it itself and nothing else is set here.  Otherwise the cache lives
at a fixed path inside the checkout (``<repo>/.jax_cache``, git-ignored):
the path is part of what a later process must find again, so it never
depends on a temp directory, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
