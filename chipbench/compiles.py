"""Counts XLA programs compiled and programs loaded from the persistent
cache, from JAX's monitoring events. Listeners stay for the process, so
make one counter per process and read differences."""
from __future__ import annotations

import os
from pathlib import Path

import jax


class CompileCounter:
    def __init__(self):
        self.programs = 0          # compile requests that reached XLA
        self.seconds = 0.0         # their wall time, cache loads included
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    @property
    def compiled(self) -> int:
        return self.programs - self.cache_hits


def enable_cache(root: Path) -> str:
    """JAX's persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR``
    when set, else the fixed directory ``<checkout>/.jax_cache``. Every
    program is cached, however quickly it compiled."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(root / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
