"""Device time per round of the client-chunked round's folds: every run
of a compiled program whose name holds ``fold`` (each chunk's weighted
updates into its cluster's accumulator, a finished cluster into its
parent's), from the profiler trace."""


def read(run):
    t = run["trace"]
    if not t or not t["chips"]:
        return None
    progs = t["chips"][t["busiest"]]["programs"]
    s = sum(v for k, v in progs.items() if "fold" in k)
    return s / run["stats"]["units"] * 1e3 if s > 0 else None
