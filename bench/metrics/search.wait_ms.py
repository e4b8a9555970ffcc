"""Host ms per swarm iteration waiting for the scores and reading them
back: the program's ``tpd.wait`` span."""


def read(run):
    try:
        from repro.utils import tracing
    except ImportError:     # a program without its own spans
        return None
    s = tracing.snapshot()["seconds"].get("tpd.wait")
    return None if s is None else s / run["stats"]["units"] * 1e3
