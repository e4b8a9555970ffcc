"""Host ms per swarm iteration in ``FlagSwapPSO.run`` outside scoring:
deduplication, history, bests and the swarm step. Self time of the
program's ``search.iteration`` span."""


def read(run):
    try:
        from repro.utils import tracing
    except ImportError:     # a program without its own spans
        return None
    s = tracing.snapshot()["self_seconds"].get("search.iteration")
    return None if s is None else s / run["stats"]["units"] * 1e3
