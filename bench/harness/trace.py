"""From a profiler trace to the per-layer numbers, in two steps.

``Profile`` records the window with ``jax.profiler`` (no Python tracer,
no HLO protos) into a temporary directory, and ``events`` condenses the
``.xplane.pb`` into plain lists:

    {"devices": {plane: {"ops": [[name, start_ns, dur_ns], ...],
                         "modules": [[name, start_ns, dur_ns], ...]}},
     "host": [[span, start_ns, dur_ns], ...]}

where a device plane is a TPU's (``/device:TPU:<n>``), ``ops`` are the
events of its ``XLA Ops`` line, each named ``<program>/<op>`` after the
run of a compiled program that holds it, and ``modules`` the events of
its ``XLA Modules`` line (one per run of a program), and ``host`` holds
the benchmark's own spans (``jax.profiler.TraceAnnotation``, by the
names that the caller gives: those the driver opened, and ``window``)
from every host thread. All times are on the profiler's clock.

``reduce`` turns those lists into numbers: the traced window (the
``window`` span), each chip's busy time (the union of its op
intervals inside the window), device time per compiled program and per
op, and the idle gaps of the busiest chip, each named after the
innermost benchmark span that covers its middle.
"""
from __future__ import annotations

import bisect
import heapq
import re
import shutil
import tempfile
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")


class Profile:
    """``with Profile() as p: ...`` traces the block;
    ``p.events(span_names)``."""

    def __enter__(self):
        import jax
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        return self

    def __exit__(self, *exc):
        import jax
        jax.profiler.stop_trace()
        return False

    def events(self, span_names) -> dict:
        try:
            paths = list(Path(self.dir).rglob("*.xplane.pb"))
            if len(paths) != 1:
                raise RuntimeError(f"expected one trace file, found "
                                   f"{len(paths)} under {self.dir}")
            return condense(paths[0].read_bytes(), span_names)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def condense(xspace: bytes, span_names) -> dict:
    """The serialized ``XSpace`` of a trace -> the plain lists above,
    keeping the host spans named in ``span_names``."""
    span_names = frozenset(span_names)
    import jax
    data = jax.profiler.ProfileData.from_serialized_xspace(xspace)
    out = {"devices": {}, "host": []}
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {line.name: line for line in plane.lines}
            mods = sorted(([e.name, int(e.start_ns), int(e.duration_ns)]
                           for e in (lines["XLA Modules"].events
                                     if "XLA Modules" in lines else ())),
                          key=lambda m: m[1])
            starts = [m[1] for m in mods]
            ops = []
            for e in (lines["XLA Ops"].events if "XLA Ops" in lines
                      else ()):
                s, d = int(e.start_ns), int(e.duration_ns)
                i = bisect.bisect_right(starts, s) - 1
                prog = program_name(mods[i][0]) \
                    if i >= 0 and s < mods[i][1] + mods[i][2] else "?"
                ops.append([f"{prog}/{op_name(e.name)}", s, d])
            out["devices"][plane.name] = {"ops": ops, "modules": mods}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"].extend([e.name, int(e.start_ns),
                                    int(e.duration_ns)]
                                   for e in line.events
                                   if e.name in span_names)
    return out


def _union(intervals: list) -> list:
    merged: list = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def _clip(intervals, lo, hi) -> list:
    return [[max(a, lo), min(b, hi)] for a, b in intervals
            if b > lo and a < hi]


def op_name(hlo: str) -> str:
    """``%fusion.1 = f32[...] fusion(...)`` -> ``fusion.1``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def program_name(module: str) -> str:
    """``jit_local_all(12)`` -> ``jit_local_all``."""
    return re.sub(r"\(\d+\)$", "", module)


def reduce(ev: dict) -> dict:
    windows = [(s, s + d) for n, s, d in ev["host"] if n == "window"]
    if len(windows) != 1:
        raise ValueError(f"the trace holds {len(windows)} window spans")
    lo, hi = windows[0]
    window_s = (hi - lo) / 1e9
    host = sorted(((s, s + d, n) for n, s, d in ev["host"]
                   if n != "window"), key=lambda x: x[0])
    chips = {}
    for name, dev in sorted(ev["devices"].items()):
        ops = [(n, s, s + d) for n, s, d in dev["ops"] if s + d > lo and s < hi]
        busy = _clip(_union([[s, e] for _, s, e in ops]), lo, hi)
        prog: dict = {}
        for n, s, d in dev["modules"]:
            if s + d > lo and s < hi:
                k = program_name(n)
                prog[k] = prog.get(k, 0.0) + d / 1e9
        per_op: dict = {}
        for n, s, e in ops:
            per_op[n] = per_op.get(n, 0.0) + (e - s) / 1e9
        chips[name] = {"busy_s": sum(b - a for a, b in busy) / 1e9,
                       "busy": busy, "programs": prog, "ops": per_op}
    if not chips:
        return {"window_s": window_s, "busy_s": 0.0, "chips": {},
                "busiest": None, "breakdown": {"device_ops": [],
                                               "idle_gaps": []}}
    busiest = max(chips, key=lambda k: chips[k]["busy_s"])
    edges = [lo] + [x for a, b in chips[busiest]["busy"] for x in (a, b)] \
        + [hi]
    gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2], strict=True)
            if b > a]
    # name only the ten longest gaps (and any tied with the tenth): naming
    # one scans the spans, and a window holds tens of thousands of gaps
    longest = heapq.nlargest(10, gaps, key=lambda g: g[1] - g[0])
    if longest:
        cut = longest[-1][1] - longest[-1][0]
        longest = [(a, b) for a, b in gaps if b - a >= cut]
    named = sorted(((b - a) / 1e9, _innermost(host, (a + b) / 2))
                   for a, b in longest)[::-1]
    ops_total: dict = {}
    for c in chips.values():
        for n, t in c["ops"].items():
            ops_total[n] = ops_total.get(n, 0.0) + t / len(chips)
    top = sorted(ops_total.items(), key=lambda kv: -kv[1])[:10]
    return {"window_s": window_s,
            "busy_s": sum(c["busy_s"] for c in chips.values()) / len(chips),
            "chips": {k: {kk: v for kk, v in c.items() if kk != "busy"}
                      for k, c in chips.items()},
            "busiest": busiest,
            "breakdown": {"device_ops": [[n, t] for n, t in top],
                          "idle_gaps": [[n, t] for t, n in named[:10]]}}


def _innermost(host: list, t: float) -> str:
    """The shortest benchmark span covering instant ``t``."""
    best = None
    for s, e, n in host:
        if s > t:
            break
        if e >= t and (best is None or e - s < best[0]):
            best = (e - s, n)
    return best[1] if best else "outside spans"
