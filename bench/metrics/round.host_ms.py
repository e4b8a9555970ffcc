"""Host ms per round in ``FederatedOrchestrator.run_round`` outside its
child spans: placement validation, the local step's dispatch and
reorder, the record. Self time of the program's ``round`` span."""


def read(run):
    try:
        from repro.utils import tracing
    except ImportError:     # a program without its own spans
        return None
    s = tracing.snapshot()["self_seconds"].get("round")
    return None if s is None else s / run["stats"]["units"] * 1e3
