"""End-to-end federated training driver (CPU-runnable).

Runs the paper's system for real: N heterogeneous clients train a model
on non-IID synthetic data; every round a placement strategy (PSO /
random / uniform / greedy / ga) proposes the aggregation tree; the
orchestrator measures the black-box TPD and feeds it back. This is the
single-host emulation of the docker/MQTT deployment (paper Sec. IV-C);
the multi-chip variant of the same round is what ``dryrun.py`` lowers.

Usage:
    PYTHONPATH=src python -m repro.launch.train \
        --arch paper-mlp-1m8 --strategy pso --rounds 50 --clients 15
"""
from __future__ import annotations

import argparse
import dataclasses
import json
from pathlib import Path

from repro.configs import get_config
from repro.core.cost_model import CostModel
from repro.core.hierarchy import ClientPool
from repro.core.registry import create_strategy, list_strategies
from repro.data.synthetic import make_federated_dataset
from repro.fl.distributed import choose_fl_hierarchy
from repro.fl.orchestrator import FederatedOrchestrator
from repro.models import get_model


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="paper-mlp-1m8")
    # only strategies constructible from (hierarchy, clients, cost_model)
    # alone — ones with required config fields (static's placement) have
    # no CLI surface here
    cli_ok = [i.name for i in list_strategies()
              if all(f.default is not dataclasses.MISSING
                     or f.default_factory is not dataclasses.MISSING
                     for f in dataclasses.fields(i.config_cls))]
    ap.add_argument("--strategy", default="pso", choices=sorted(cli_ok))
    ap.add_argument("--rounds", type=int, default=50)
    ap.add_argument("--clients", type=int, default=15)
    ap.add_argument("--local-steps", type=int, default=2)
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--reduced", action="store_true",
                    help="use the reduced (smoke) config of --arch")
    ap.add_argument("--out", default=None, help="write round records JSON")
    ap.add_argument("--verbose", action="store_true")
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.reduced or cfg.family != "mlp":
        # transformer archs run their reduced variant on CPU
        cfg = cfg.reduced() if cfg.family != "mlp" else cfg
    model = get_model(cfg)

    hierarchy = choose_fl_hierarchy(args.clients)
    clients = ClientPool.random(hierarchy.total_clients, seed=args.seed)
    data = make_federated_dataset(
        cfg, n_clients=hierarchy.total_clients, seed=args.seed)

    strategy = create_strategy(
        args.strategy, hierarchy, seed=args.seed, clients=clients,
        cost_model=CostModel(hierarchy, clients))
    orch = FederatedOrchestrator(
        model, hierarchy, clients, data,
        local_steps=args.local_steps, batch_size=args.batch_size,
        seed=args.seed)
    result = orch.run(strategy, rounds=args.rounds, verbose=args.verbose)
    summary = result.summary()
    print(json.dumps(summary, indent=2))
    if args.out:
        Path(args.out).write_text(json.dumps({
            "summary": summary,
            "rounds": [vars(r) for r in result.rounds],
        }, indent=1, default=str))
    return 0


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    raise SystemExit(main())
