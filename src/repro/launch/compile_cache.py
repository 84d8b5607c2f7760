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


TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileCounter:
    """Counts JAX's compile events while entered, through ``jax.monitoring``.

    ``traces``: functions traced to a jaxpr.  ``compiles``: programs built
    for the backend, with their summed seconds in ``compile_s``; one loaded
    from the persistent cache counts too, and also as one of
    ``cache_hits``.  The listeners run only when such an event occurs and
    are removed on exit.
    """

    def __init__(self):
        self.traces = self.compiles = self.cache_hits = 0
        self.compile_s = 0.0

    def _on_duration(self, event: str, secs: float, **_):
        if event == TRACE_EVENT:
            self.traces += 1
        elif event == COMPILE_EVENT:
            self.compiles += 1
            self.compile_s += secs

    def _on_event(self, event: str, **_):
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1

    def __enter__(self) -> "CompileCounter":
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc) -> None:
        from jax import monitoring

        monitoring.unregister_event_duration_listener(self._on_duration)
        monitoring.unregister_event_listener(self._on_event)

    def counts(self) -> dict:
        return {"traces": self.traces, "compiles": self.compiles,
                "compile_s": self.compile_s, "cache_hits": self.cache_hits}

    def __str__(self) -> str:
        return (f"traces={self.traces} compiles={self.compiles} "
                f"compile_s={self.compile_s:.3f} cache_hits={self.cache_hits}")
