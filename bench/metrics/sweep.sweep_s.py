"""Host seconds per sweep: the benchmark's span around each
``run_experiment`` call (its pools, strategies, and every round with
its pooled scoring call, which compiles the sharded evaluator's
operations anew while ``shard_map`` runs eagerly)."""


def read(run):
    s = run["spans"].get("sweep", 0.0)
    sweeps = run["stats"]["units"] / run["facts"]["rounds_per_sweep"]
    return s / sweeps if s > 0 else None
