"""Persistent compilation cache for the entry points.

``chip_smoke.py``, ``python -m repro.launch.serve`` and
``benchmarks/run.py`` call :func:`enable_compile_cache` once at start-up, so a
second run in the same checkout reuses compiled kernels and executables.
Library modules never call it. Where ``JAX_COMPILATION_CACHE_DIR`` is set,
JAX reads it itself and nothing else is configured; otherwise the cache lives
in ``<checkout>/.jax_cache/`` — a fixed path, because the path is part of
what makes an entry found again.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CACHE_EVENTS = ("/jax/compilation_cache/cache_hits",
                "/jax/compilation_cache/cache_misses")


def enable_compile_cache(checkout: str | Path) -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax

    path = str(Path(checkout).resolve() / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


class CacheCounter:
    """Counts persistent-cache hits and misses from JAX's monitoring events
    (the proof that a second run found the first run's entries)."""

    def __init__(self) -> None:
        import jax

        self.counts = {e: 0 for e in CACHE_EVENTS}
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **kwargs) -> None:
        if event in self.counts:
            self.counts[event] += 1

    @property
    def hits(self) -> int:
        return self.counts[CACHE_EVENTS[0]]

    @property
    def misses(self) -> int:
        return self.counts[CACHE_EVENTS[1]]
