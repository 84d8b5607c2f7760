"""Persistent XLA compilation cache for the entry points that use a chip.

Called from the ``main`` of ``chip_smoke.py`` and the ``serve``/``train``
launchers, never at import and never from tests.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, jax already reads it and nothing is
set here.  Otherwise the cache lives at the fixed path ``<repo>/.jax_cache``:
the path is part of the cache key, so it must not move between runs.
"""
from __future__ import annotations

import os
from pathlib import Path

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent cache; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
