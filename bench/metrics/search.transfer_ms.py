"""Host ms per swarm iteration uploading the scoring's three inputs and
calling ``batch_tpd_pallas`` until it returns: the program's
``tpd.transfer`` span."""


def read(run):
    try:
        from repro.utils import tracing
    except ImportError:     # a program without its own spans
        return None
    s = tracing.snapshot()["seconds"].get("tpd.transfer")
    return None if s is None else s / run["stats"]["units"] * 1e3
