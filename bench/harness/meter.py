"""Host-side meters: compile seconds and the benchmark's own spans."""
from __future__ import annotations

import contextlib
import time
from collections import defaultdict


class CompileMeter:
    """Seconds spent in XLA compilation, compiles and persistent-cache
    hits, read from ``jax.monitoring`` events (a cache hit's retrieval
    counts as compile time)."""

    def __init__(self):
        import jax
        self.compile_s = 0.0
        self.compiles = self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


class Spans:
    """Named host spans around the program's calls.

    Each span adds its wall seconds to ``seconds[name]`` and counts into
    ``count[name]``; with ``annotate`` on, it is also written into the
    profiler's trace as a ``jax.profiler.TraceAnnotation`` of that name,
    on the profiler's own clock."""

    def __init__(self, annotate: bool = False):
        self.annotate = annotate
        self.seconds: dict = defaultdict(float)
        self.count: dict = defaultdict(int)

    @contextlib.contextmanager
    def __call__(self, name: str):
        ann = contextlib.nullcontext()
        if self.annotate:
            import jax
            ann = jax.profiler.TraceAnnotation(name)
        with ann:
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.seconds[name] += time.perf_counter() - t0
                self.count[name] += 1

    def names(self) -> set:
        """Every span name opened since the last reset."""
        return set(self.count)

    def reset(self) -> None:
        self.seconds.clear()
        self.count.clear()
