"""Placement-strategy sweeps over a simulated pool, back to back.

One sweep is the program's ``run_experiment(scenario, strategies,
rounds, seeds, mode="batched")``: every (strategy, seed) run advances
round by round in lockstep, and each round's placements of all runs are
scored in one pooled float64 call (``PooledTPDEvaluator``), whose rows
the program shards over every local chip (``shard_map`` row shards and
a psum merge) when more than one is visible. Each sweep draws fresh
seeds from ``--seed``, so each builds fresh pools.

Set-up runs one whole sweep (imports, pools, strategies, the first
compile of the sharded evaluator). The window runs sweeps until one
ends past ``--seconds``. The benchmark sees the scored rows through a
subclass of the evaluator that the runner is handed for the cell's
lifetime: each call's placements and TPDs, and each pool's attributes,
are kept for the comparison against the configuration's float64 eq.
6/7 (``large-10k.py``).
"""
from __future__ import annotations

import time

import numpy as np

from harness import gen, tree


class Cell:
    unit = "rounds"

    def __init__(self, cfg, traffic, seed, ref, spans):
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.ref, self.spans = ref, spans
        self.calls: list = []   # (pools, rows, placements, tpds) per call
        self.sweeps = 0

    def setup(self) -> None:
        from repro.experiments import runner
        from repro.experiments.eval_config import EvalConfig
        from repro.experiments.scenarios import get_scenario

        cfg, tr = self.cfg, self.traffic
        spec = get_scenario(tr["scenario"])
        if (spec.n_clients, spec.depth, spec.width) != \
                (cfg["clients"], cfg["depth"], cfg["width"]):
            raise ValueError(f"scenario {spec.name!r} is not the "
                             f"configuration's deployment")
        self.spec = spec
        self.eval_config = EvalConfig(mode="batched")
        cell = self

        class Scored(runner.PooledTPDEvaluator):
            """The program's evaluator; keeps what it scored."""

            def tpds(self, placements, pool_idx=None):
                with cell.spans("score"):
                    out = super().tpds(placements, pool_idx)
                if not hasattr(self, "kept_pools"):
                    self.kept_pools = [
                        {k: np.array(getattr(m.clients, k), np.float64)
                         for k in ("mdatasize", "pspeed", "memcap")}
                        for m in self.models]
                rows = np.arange(len(placements)) if pool_idx is None \
                    else np.asarray(pool_idx)
                cell.calls.append((self.kept_pools, rows,
                                   np.array(placements, np.int64),
                                   np.array(out, np.float64)))
                return out

        self._program_evaluator = runner.PooledTPDEvaluator
        runner.PooledTPDEvaluator = Scored
        self._sweep()

    def _sweep(self) -> int:
        """One sweep; returns the rows it scored."""
        from repro.experiments.runner import run_experiment
        tr = self.traffic
        seeds = [gen.seed31(self.seed, gen.PROGRAM, self.sweeps, i)
                 for i in range(tr["seeds"])]
        before = len(self.calls)
        with self.spans("sweep"):
            run_experiment(self.spec, tr["strategies"], rounds=tr["rounds"],
                           seeds=seeds, progress=False,
                           eval_config=self.eval_config)
        self.sweeps += 1
        return sum(len(c[2]) for c in self.calls[before:])

    def window(self, seconds: float) -> dict:
        rows = sweeps = 0
        t0 = time.perf_counter()
        while True:
            rows += self._sweep()
            sweeps += 1
            elapsed = time.perf_counter() - t0
            if elapsed >= seconds:
                break
        rounds = sweeps * self.traffic["rounds"]
        return {"elapsed": elapsed, "units": rounds,
                "placements_per_s": rows / elapsed}

    def facts(self) -> dict:
        return {"rounds_per_sweep": self.traffic["rounds"]}

    def release(self) -> None:
        from repro.experiments import runner
        runner.PooledTPDEvaluator = self._program_evaluator

    # ------------------------------------------------------------------
    def outputs(self, who: str) -> dict:
        """Every scored row: the program's TPDs, or the control's (the
        reference one precision down, float32)."""
        tpds = [c[3] for c in self.calls]
        if who != "program":
            tpds = self._tpd(np.float32)
        return {"tpds": tpds}

    def _tpd(self, dtype=np.float64) -> list:
        """The reference's TPD of every scored row, one call per pool."""
        cfg = self.cfg
        out = [np.empty(len(c[2]), np.float64) for c in self.calls]
        by_pool: dict = {}
        for k, (pools, rows, _, _) in enumerate(self.calls):
            for i, r in enumerate(rows):
                by_pool.setdefault((id(pools), int(r)), (pools[r], []))[
                    1].append((k, i))
        for pool, at in by_pool.values():
            placements = np.stack([self.calls[k][2][i] for k, i in at])
            tpd = self.ref.tpd(placements, pool, cfg["depth"], cfg["width"],
                               cfg["memory_penalty"], dtype)
            for (k, i), v in zip(at, tpd, strict=True):
                out[k][i] = v
        return out

    def compare(self, got: dict) -> dict:
        want = self._tpd()
        placements = np.concatenate([c[2] for c in self.calls])
        valid = tree.valid_rows(placements, self.cfg["clients"])
        gaps = np.abs(np.concatenate(got["tpds"]) - np.concatenate(want)) \
            / np.concatenate(want)
        gaps = np.where(valid, gaps, np.inf)
        return {"numbers": {"tpd_gap": float(np.max(gaps)),
                            "invalid_placements": float(np.sum(~valid))},
                "answers": gaps, "answer_limit": "tpd_gap"}
