"""Spans and counters at the tuning service's layer boundaries.

``span(name, **stats)`` is a ``jax.profiler.TraceAnnotation``, so the
service's spans land in a profiler trace on the device's clock.  Whether
a profiler session is recording is the annotation's own check
(``TraceAnnotation.is_enabled()``, the trace recorder's flag); with no
session, ``span`` returns a shared no-op and builds nothing, so it costs
that one check.  A stat given as a callable is called only while
recording, for stats costly to build; ``set_metadata`` adds stats known
only once the span is open.  Spans are named ``mango.<what>``.
This module never imports JAX itself: a process without JAX records
nothing, and the service's HTTP layer stays importable without it.

``Counters`` are an instance's integer counters, always on: what an
operator reads (``GET /stats``).  ``add`` is safe from any thread.
"""
from __future__ import annotations

import sys
import threading
import time
from typing import Any, Dict, Iterable


def _built(stats: Dict[str, Any]) -> Dict[str, Any]:
    return {k: v() if callable(v) else v for k, v in stats.items()}


class _Idle:
    """The span while no profiler session records."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> None:
        return None

    def set_metadata(self, **stats) -> None:
        return None


_IDLE = _Idle()


def span(name: str, **stats):
    prof = sys.modules.get("jax.profiler")
    if prof is None or not prof.TraceAnnotation.is_enabled():
        return _IDLE
    return prof.TraceAnnotation(name, **_built(stats))


class Counters:
    """Named integer counters of one service or bank instance."""

    def __init__(self, names: Iterable[str] = ()):
        self._names = tuple(names)
        self._lock = threading.Lock()
        self._values = dict.fromkeys(self._names, 0)

    def add(self, name: str, v: int = 1) -> None:
        with self._lock:
            self._values[name] = self._values.get(name, 0) + int(v)

    def snapshot(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._values)

    def clear(self) -> None:
        with self._lock:
            self._values = dict.fromkeys(self._names, 0)

    def timed(self, name: str) -> "_Timer":
        """A context that adds its elapsed nanoseconds to ``name``."""
        return _Timer(self, name)


class _Timer:
    __slots__ = ("_counters", "_name", "_t")

    def __init__(self, counters: Counters, name: str):
        self._counters, self._name = counters, name

    def __enter__(self):
        self._t = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        self._counters.add(self._name, time.perf_counter_ns() - self._t)
