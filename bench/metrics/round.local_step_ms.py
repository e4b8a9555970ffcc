"""Device time per round of the clients' local training: every run of a
compiled program whose name holds ``local_all`` (the round engine's
vmap-of-scan over clients), from the profiler trace."""
PROGRAMS = ("local_all",)


def read(run):
    t = run["trace"]
    if not t or not t["chips"]:
        return None
    progs = t["chips"][t["busiest"]]["programs"]
    s = sum(v for k, v in progs.items() if any(p in k for p in PROGRAMS))
    return s / run["stats"]["units"] * 1e3 if s > 0 else None
