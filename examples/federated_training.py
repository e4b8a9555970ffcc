"""End-to-end driver: federated training of the paper's ~1.8M-param MLP
with PSO-optimized aggregation placement (the docker experiment of
Sec. IV-C, single-host emulation).

15 heterogeneous clients train on non-IID Dirichlet partitions; Flag-Swap
tests one particle placement per round against the round delay and
converges to a fast tree, while random keeps paying for slow aggregation
hosts.

The run is one ad-hoc ScenarioSpec (kind='emulated') swept through the
unified experiment API — the same path as
``python -m repro.experiments run paper-fig4``.

Run:  PYTHONPATH=src python examples/federated_training.py [--rounds 200]
"""
import argparse

from repro.experiments import ScenarioSpec, run_experiment
from repro.fl.distributed import choose_fl_hierarchy

ap = argparse.ArgumentParser()
ap.add_argument("--rounds", type=int, default=200)
ap.add_argument("--clients", type=int, default=15)
ap.add_argument("--strategies", nargs="+",
                default=["pso", "random", "uniform"])
args = ap.parse_args()

hierarchy = choose_fl_hierarchy(args.clients)
print(f"{args.clients} clients, hierarchy depth={hierarchy.depth} "
      f"width={hierarchy.width} ({hierarchy.dimensions} aggregator slots)")

spec = ScenarioSpec(
    name="federated-training", kind="emulated",
    depth=hierarchy.depth, width=hierarchy.width,
    trainers_per_leaf=hierarchy.trainers_per_leaf,
    n_clients=hierarchy.total_clients,
    model="paper-mlp-1m8", local_steps=2, batch_size=32,
    rounds=args.rounds,
    description="choose_fl_hierarchy-sized emulated MLP training")

result = run_experiment(spec, args.strategies, rounds=args.rounds,
                        seeds=(0,))

agg = result.aggregates
if "pso" in agg and "random" in agg:
    save = 1 - agg["pso"]["total_tpd"] / agg["random"]["total_tpd"]
    print(f"\nPSO total processing time is {save:.1%} lower than random "
          f"placement (paper reports ~43% on the docker cluster).")
