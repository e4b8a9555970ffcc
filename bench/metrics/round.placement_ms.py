"""Host wall time per round inside the placement strategy: the
benchmark's spans around ``strategy.propose`` and ``strategy.observe``."""


def read(run):
    s = run["spans"].get("propose", 0.0) + run["spans"].get("observe", 0.0)
    return s / run["stats"]["units"] * 1e3 if s > 0 else None
