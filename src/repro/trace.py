"""Spans at the store's layer boundaries, on the profiler's clock.

``span(name, **meta)`` is a context manager. While a JAX profiler session
records, it is a ``jax.profiler.TraceAnnotation`` of ``name``, which lands
in the profiler's own trace on the calling thread, beside the device's
ops; with no session recording it is a shared no-op, so a span costs one
check. Its time goes to the counters separately: a caller reads ``clock``
(``perf_counter_ns``) at each phase boundary, beside the span, keeps the
differences in locals and adds them to its counters once per call. There
is no span buffer: the profiler keeps the spans.

``install_gc_hook`` times every pass of Python's garbage collector, as
``gc.gen<N>`` spans and the process-wide counts that ``gc_counters``
returns.
"""
from __future__ import annotations

import functools
import gc
import threading
import time

from jax._src.lib import _profiler
from jax.profiler import TraceAnnotation

_recording = _profiler.TraceMe.is_enabled
clock = time.perf_counter_ns


class _Off:
    """The span of a call no profiler records."""

    __slots__ = ()

    def __enter__(self) -> None:
        return None

    def __exit__(self, *exc) -> None:
        return None


_OFF = _Off()


def span(name: str, **meta):
    """A ``TraceAnnotation`` of ``name`` (``meta`` attached to the event)
    while a profiler session records, else a no-op."""
    return TraceAnnotation(name, **meta) if _recording() else _OFF


def traced(name: str):
    """Decorator: run the whole function inside ``span(name)``."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with span(name):
                return fn(*args, **kwargs)
        return inner
    return wrap


class _GcClock:
    """Counts and wall time of the collector's passes. One per process, as
    the collector is: CPython runs one collection at a time, so its start
    and stop callbacks come in pairs on one thread."""

    def __init__(self):
        self.collections = 0
        self.full_collections = 0
        self.pause_ns = 0
        self._t0 = 0
        self._ann = None

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            if _recording():
                self._ann = TraceAnnotation(f"gc.gen{info['generation']}")
                self._ann.__enter__()
            self._t0 = clock()
            return
        self.pause_ns += clock() - self._t0
        self.collections += 1
        self.full_collections += int(info["generation"] == 2)
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None


_GC = _GcClock()
_gc_install = threading.Lock()


def install_gc_hook() -> None:
    """Add the collector's timer to ``gc.callbacks``, once per process."""
    with _gc_install:
        if _GC not in gc.callbacks:
            gc.callbacks.append(_GC)


def gc_counters() -> dict:
    """The collector's passes since the hook was installed, process-wide."""
    return {"gc_collections": _GC.collections,
            "gc_full_collections": _GC.full_collections,
            "gc_pause_ns": _GC.pause_ns}
