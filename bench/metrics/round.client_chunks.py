"""Client chunks trained per round (one ``local_all`` dispatch each, then
their folds): the program's ``round.client_chunks`` counter."""


def read(run):
    try:
        from repro.utils import tracing
    except ImportError:     # a program without its own spans
        return None
    n = tracing.snapshot()["counters"].get("round.client_chunks")
    return None if n is None else n / run["stats"]["units"]
