"""The program's host spans and counters, on the profiler's clock.

Tracing is on exactly while a JAX profiler session records: inside
``jax.profiler.trace(...)``, between ``start_trace`` and ``stop_trace``,
or during a capture through ``jax.profiler.start_server``. It has no
option of its own.

- ``span(name, **ids)`` is a context manager. Off, it returns a shared
  no-op after one check. On, it opens ``jax.profiler.TraceAnnotation
  (name, **ids)``, so the span lands in the profiler's trace on the same
  clock as the device's operations, and adds to the totals of its name:
  its wall seconds, its self seconds (less the part that its child spans
  on the same thread cover) and one to its count.
- ``count(name, n)`` adds to a counter, on the same gate.
- ``recording()`` says whether the gate is open, for a caller whose
  count costs work of its own (a read from the device).
- ``snapshot()`` returns ``{"seconds", "self_seconds", "count",
  "counters"}``; ``reset()`` clears them.

No event is kept in memory: the profiler's trace is the export.
"""
from __future__ import annotations

import threading
import time
from collections import defaultdict

from jax.profiler import TraceAnnotation

_recording = TraceAnnotation.is_enabled


class _Off:
    """The span handed out while no profiler session records."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


OFF = _Off()


class _Stack(threading.local):
    def __init__(self):
        self.open: list = []


class _Span:
    __slots__ = ("_tracer", "_name", "_ann", "_t0", "_children")

    def __init__(self, tracer: "Tracer", name: str, ids: dict):
        self._tracer, self._name = tracer, name
        self._ann = TraceAnnotation(name, **ids)
        self._children = 0.0

    def __enter__(self):
        self._ann.__enter__()
        self._tracer._stack.open.append(self)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self._t0
        stack = self._tracer._stack.open
        stack.pop()
        if stack:
            stack[-1]._children += dt
        self._tracer._add(self._name, dt, dt - self._children)
        self._ann.__exit__(*exc)
        return False


class Tracer:
    """Per-name totals of spans and counters, kept while a profiler
    session records."""

    def __init__(self):
        self._lock = threading.Lock()
        self._stack = _Stack()
        self.reset()

    def span(self, name: str, **ids):
        return _Span(self, name, ids) if _recording() else OFF

    def count(self, name: str, n: int = 1) -> None:
        if _recording():
            with self._lock:
                self._counters[name] += n

    def _add(self, name: str, seconds: float, self_seconds: float) -> None:
        with self._lock:
            self._seconds[name] += seconds
            self._self[name] += self_seconds
            self._count[name] += 1

    def snapshot(self) -> dict:
        with self._lock:
            return {"seconds": dict(self._seconds),
                    "self_seconds": dict(self._self),
                    "count": dict(self._count),
                    "counters": dict(self._counters)}

    def reset(self) -> None:
        with self._lock:
            self._seconds: dict = defaultdict(float)
            self._self: dict = defaultdict(float)
            self._count: dict = defaultdict(int)
            self._counters: dict = defaultdict(int)


# the process's tracer: the profiler it follows is process-wide too
TRACER = Tracer()
span = TRACER.span
count = TRACER.count
recording = _recording
snapshot = TRACER.snapshot
reset = TRACER.reset
