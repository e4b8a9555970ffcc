"""Host ms per round in the evaluation (``_evaluate``: the evaluation
batch, its transfer and dispatch), less the readback it waits on. Self
time of the program's ``round.eval`` span."""


def read(run):
    try:
        from repro.utils import tracing
    except ImportError:     # a program without its own spans
        return None
    s = tracing.snapshot()["self_seconds"].get("round.eval")
    return None if s is None else s / run["stats"]["units"] * 1e3
