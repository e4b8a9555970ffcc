"""Host wall time per swarm iteration outside the scoring call: the PSO
update, deduplication and bookkeeping of ``FlagSwapPSO.run``."""


def read(run):
    s = run["spans"].get("pso_update", 0.0) - \
        run["spans"].get("tpd_scoring", 0.0)
    return s / run["stats"]["units"] * 1e3 if s > 0 else None
