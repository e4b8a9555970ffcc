"""Share of the program's TPD scoring calls whose trainer-split leaf loads
were built on the device, inside the scoring program: its
``tpd.device_leaf_calls`` counter over its ``tpd.calls``."""


def read(run):
    try:
        from repro.utils import tracing
    except ImportError:     # a program without its own spans
        return None
    counters = tracing.snapshot()["counters"]
    calls = counters.get("tpd.calls")
    return None if not calls else \
        counters.get("tpd.device_leaf_calls", 0) / calls
