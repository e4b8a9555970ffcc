"""Host ms per round in the per-level merge (``_agg_batched``: the round
plan, the per-level loads and the merge's dispatch). Self time of the
program's ``round.merge`` span."""


def read(run):
    try:
        from repro.utils import tracing
    except ImportError:     # a program without its own spans
        return None
    s = tracing.snapshot()["self_seconds"].get("round.merge")
    return None if s is None else s / run["stats"]["units"] * 1e3
