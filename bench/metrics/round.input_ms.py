"""Host ms per round assembling the clients' step batches
(``FederatedOrchestrator._collect_batches``): the program's
``round.inputs`` span."""


def read(run):
    try:
        from repro.utils import tracing
    except ImportError:     # a program without its own spans
        return None
    s = tracing.snapshot()["seconds"].get("round.inputs")
    return None if s is None else s / run["stats"]["units"] * 1e3
