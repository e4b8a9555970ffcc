"""``local_all`` dispatches per round, one per batch-shape bucket of the
cohort: the program's ``round.local_calls`` counter."""


def read(run):
    try:
        from repro.utils import tracing
    except ImportError:     # a program without its own spans
        return None
    n = tracing.snapshot()["counters"].get("round.local_calls")
    return None if n is None else n / run["stats"]["units"]
