"""Share of the round's ``local_all`` dispatches that gather their batches
on the device from an index table (the sample set resident there): the
program's ``round.indexed_calls`` counter over its ``round.local_calls``."""


def read(run):
    try:
        from repro.utils import tracing
    except ImportError:     # a program without its own spans
        return None
    counters = tracing.snapshot()["counters"]
    indexed = counters.get("round.indexed_calls")
    calls = counters.get("round.local_calls")
    return None if indexed is None or not calls else indexed / calls
