"""Host ms per round blocked on the device: the program's ``round.wait``
spans, around the wait for the local step and the evaluation's
readback."""


def read(run):
    try:
        from repro.utils import tracing
    except ImportError:     # a program without its own spans
        return None
    s = tracing.snapshot()["seconds"].get("round.wait")
    return None if s is None else s / run["stats"]["units"] * 1e3
