"""Paper Fig. 4: the docker/MQTT cluster experiment, emulated.

Scenario (Sec. IV-C): 10 clients — one beefy (3 cores / 2 GB), two
medium (1 core / 1 GB), seven tiny (1 core / 64 MB) — train the paper's
1.8M-param MLP for 50 rounds under three placement strategies: random,
uniform round-robin, and PSO (Flag-Swap). The TPD per round is what the
emulated round charges (each client's local steps and each cluster's
eq. 6 payload, scaled by the emulated per-client speed — the docker
cpu-limit analogue): the optimizer only ever sees that number, black-box
exactly as deployed.

The paper's claims this harness checks:
  * PSO converges around round ~10;
  * after convergence PSO rounds are faster than random/uniform;
  * total processing time: PSO < uniform < random (paper: ~43% vs
    random, ~32% vs uniform in minutes saved).

Beyond paper: also runs the GA baseline and the telemetry-cheating
greedy placement (upper bound) for context.

This is now a thin wrapper over the unified experiment API: the cluster
lives in the registered ``paper-fig4`` ScenarioSpec and every strategy
is swept through ``run_experiment`` (equivalently:
``python -m repro.experiments run paper-fig4 ...``).
"""
from __future__ import annotations

import json
from pathlib import Path

from repro.experiments import get_scenario, run_experiment

OUT = Path(__file__).resolve().parent.parent / "artifacts" / "benchmarks"


def main(rounds: int = 50, seed: int = 0, n_seeds: int = 1,
         strategies=("random", "uniform", "pso", "ga", "greedy")) -> dict:
    print(f"== Fig. 4: 10-client heterogeneous cluster, {rounds} rounds, "
          f"{n_seeds} seed(s) ==")
    spec = get_scenario("paper-fig4")
    seeds = [seed + 17 * i for i in range(n_seeds)]
    result = run_experiment(spec, list(strategies), rounds=rounds,
                            seeds=seeds)

    # reshape into the historical fig4_cluster.json layout
    results = {}
    for s, agg in result.aggregates.items():
        per_seed = []
        for run in result.runs_for(s):
            per_seed.append({
                "strategy": s, "rounds": rounds,
                "total_tpd": run.total_tpd, "mean_tpd": run.mean_tpd,
                "last10_mean_tpd": run.last10_mean_tpd,
                "final_accuracy": run.final_metrics().get("accuracy", 0.0),
                "per_round_tpd": run.tpds,
                "per_round_acc": run.metrics.get("accuracy", []),
            })
        results[s] = {
            "total_tpd": agg["total_tpd"],
            "total_tpd_std": agg["total_tpd_std"],
            "mean_tpd": agg["mean_tpd"],
            "last10_mean_tpd": agg["last10_mean_tpd"],
            "final_accuracy": agg.get("final_accuracy", 0.0),
            "per_seed": per_seed,
        }

    summary = {"rounds": rounds, "n_seeds": n_seeds, "results": results}
    if {"pso", "random", "uniform"} <= set(results):
        pso_t = results["pso"]["total_tpd"]
        rnd_t = results["random"]["total_tpd"]
        uni_t = results["uniform"]["total_tpd"]
        summary["claims"] = {
            "pso_vs_random_saving": 1 - pso_t / rnd_t,
            "pso_vs_uniform_saving": 1 - pso_t / uni_t,
            "pso_faster_than_random": pso_t < rnd_t,
            "pso_faster_than_uniform": pso_t < uni_t,
        }
        print(f"-> PSO saves {summary['claims']['pso_vs_random_saving']:.1%} "
              f"vs random, {summary['claims']['pso_vs_uniform_saving']:.1%} "
              f"vs uniform (paper: ~43% / ~32% in minutes)")
    OUT.mkdir(parents=True, exist_ok=True)
    (OUT / "fig4_cluster.json").write_text(json.dumps(summary, indent=1))
    return summary


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seeds", type=int, default=1, dest="n_seeds")
    args = ap.parse_args()
    main(rounds=args.rounds, seed=args.seed, n_seeds=args.n_seeds)
