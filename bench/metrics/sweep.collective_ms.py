"""Device time per sweep round of the collectives on the busiest chip:
every op whose name holds ``all-reduce`` or ``all-gather`` (the psum
that merges the pooled evaluator's row shards, which the TPU compiler
may turn into an all-gather of disjoint segments), from the profiler
trace."""
NAMES = ("all-reduce", "all-gather")


def read(run):
    t = run["trace"]
    if not t or not t["chips"]:
        return None
    ops = t["chips"][t["busiest"]]["ops"]
    s = sum(v for k, v in ops.items()
            if any(n in k.rsplit("/", 1)[-1] for n in NAMES))
    return s / run["stats"]["units"] * 1e3 if s > 0 else None
