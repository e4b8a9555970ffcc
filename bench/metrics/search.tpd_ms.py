"""Host wall time per swarm iteration inside the program's scoring call
(``CostModel.batch_fitness``: host prologue, transfers and kernel)."""


def read(run):
    s = run["spans"].get("tpd_scoring", 0.0)
    return s / run["stats"]["units"] * 1e3 if s > 0 else None
