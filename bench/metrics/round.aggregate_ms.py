"""Device time per round of the per-level merge: every run of a compiled
program whose name holds ``fused`` (``SegmentAggregator.aggregate_fused``,
all levels in one call) or ``reduce_level`` (one level per call), from
the profiler trace."""
PROGRAMS = ("fused", "reduce_level")


def read(run):
    t = run["trace"]
    if not t or not t["chips"]:
        return None
    progs = t["chips"][t["busiest"]]["programs"]
    s = sum(v for k, v in progs.items() if any(p in k for p in PROGRAMS))
    return s / run["stats"]["units"] * 1e3 if s > 0 else None
