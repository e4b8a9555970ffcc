"""Host ms per swarm iteration in the Pallas scoring's host prologue
(the placements' array, the two ``bincount``s, the ``cumsum``): the
program's ``tpd.prologue`` span."""


def read(run):
    try:
        from repro.utils import tracing
    except ImportError:     # a program without its own spans
        return None
    s = tracing.snapshot()["seconds"].get("tpd.prologue")
    return None if s is None else s / run["stats"]["units"] * 1e3
