"""Share of the traced window in which no operation ran on the chip:
1 - (union of its op intervals) / window."""


def read(run):
    t = run["trace"]
    if not t or not t["chips"]:
        return None
    return 100.0 * (1.0 - t["chips"][t["busiest"]]["busy_s"] / t["window_s"])
