"""Offline placement search: ``FlagSwapPSO.run`` scoring whole swarms
through ``CostModel.batch_fitness`` (paper Algorithm 1), iterated until
the window closes.

The program picks the scoring path itself (``CostModel.tpd_path``: the
compiled Pallas kernel on a TPU above ``_NP_FASTPATH_ELEMS``). The
benchmark passes a thin wrapper as ``batch_fitness_fn``: a span around
the program's call, a uniform sample of the scored batches, drawn from
the seed, kept for the comparison, the best score of every batch with
its placement (the gbest that the search returns has to be it), and,
over the window's first ``still_steps`` steps, how many particles'
placements stayed as they were (the swarm has to move).
"""
from __future__ import annotations

import time

import numpy as np

from harness import gen, tree


class Cell:
    unit = "iterations"

    def __init__(self, cfg, traffic, seed, ref, spans):
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.ref, self.spans = ref, spans
        self.kept: list = []        # sampled (placements, fitness)
        self.seen = 0               # scored batches in the window
        self.sampler = gen.stream(seed, gen.SAMPLE)
        self.scored_min = np.inf    # least TPD scored, and its placement
        self.scored_best = None
        self.prev = None            # the batch scored last
        self.still = self.steps = 0

    def setup(self) -> None:
        from repro.core.cost_model import CostModel
        from repro.core.hierarchy import ClientPool, Hierarchy
        from repro.core.pso import FlagSwapPSO

        cfg, tr = self.cfg, self.traffic
        C = cfg["clients"]
        self.pool = gen.pool(cfg["pool"], C, gen.stream(self.seed, gen.POOL))
        h = Hierarchy(depth=cfg["depth"], width=cfg["width"],
                      trainers_per_leaf=cfg["trainers_per_leaf"],
                      n_clients=C)
        if (h.total_clients, h.dimensions) != (C, cfg["slots"]):
            raise ValueError(f"the tree has {h.dimensions} slots for "
                             f"{h.total_clients} clients")
        self.cm = CostModel(
            h, ClientPool(**{k: v.copy() for k, v in self.pool.items()}),
            memory_penalty=cfg["memory_penalty"])
        if tr.get("tpd_backend"):
            self.cm.set_default_backend(tr["tpd_backend"])
        self.pso = FlagSwapPSO(h.dimensions, C,
                               n_particles=tr["particles"],
                               seed=gen.seed31(self.seed), **tr["pso"])
        # warm the scoring path on the swarm's shape; the swarm does not move
        self.cm.batch_fitness(self.pso.placements())

    def _score(self, placements):
        with self.spans("tpd_scoring"):
            fitness = self.cm.batch_fitness(placements)
        tpd = -np.asarray(fitness, np.float64)
        i = int(np.argmin(tpd))
        if tpd[i] < self.scored_min:
            self.scored_min, self.scored_best = float(tpd[i]), placements[i]
        if self.prev is not None and self.steps < self.traffic["still_steps"]:
            self.still += int(np.all(placements == self.prev, axis=1).sum())
            self.steps += 1
        self.prev = placements
        # reservoir sample of the scored batches, uniform over the window
        k, cap = self.seen, self.traffic["checked_batches"]
        if k < cap:
            self.kept.append((placements, np.array(fitness)))
        else:
            j = int(self.sampler.integers(0, k + 1))
            if j < cap:
                self.kept[j] = (placements, np.array(fitness))
        self.seen += 1
        return fitness

    def window(self, seconds: float) -> dict:
        chunk = self.traffic["iterations_per_call"]
        t0 = time.perf_counter()
        n = 0
        while True:
            with self.spans("pso_update"):
                self.best = self.pso.run(self.cm.fitness, iterations=chunk,
                                         batch_fitness_fn=self._score)
            n += chunk
            elapsed = time.perf_counter() - t0
            if elapsed >= seconds:
                break
        P = self.traffic["particles"]
        return {"elapsed": elapsed, "units": n,
                "placements_per_s": P * n / elapsed}

    def facts(self) -> dict:
        from harness import work
        cfg = self.cfg
        ops, nbytes = work.tpd_work(self.traffic["particles"], cfg["slots"],
                                    cfg["clients"], cfg["depth"],
                                    cfg["memory_penalty"] > 0)
        return {"tpd_ops_per_unit": ops, "tpd_bytes_per_unit": nbytes}

    def release(self) -> None:
        self.gbest_f = float(self.pso.gbest_f)
        self.best = np.asarray(self.best, np.int64)
        self.cm = self.pso = None

    # ------------------------------------------------------------------
    def outputs(self, who: str) -> dict:
        placements = np.stack([p for p, _ in self.kept])      # (K, P, D)
        still = self.still / (self.steps * placements.shape[1]) \
            if self.steps else 1.0
        if who == "program":
            tpd = -np.stack([f for _, f in self.kept]).astype(np.float64)
            return {"placements": placements, "tpd": tpd, "best": self.best,
                    "best_tpd": -self.gbest_f, "scored_min": self.scored_min,
                    "scored_best": self.scored_best, "swarm_still": still}
        # the control scores the program's swarms in its own precision
        import ml_dtypes
        tpd = self._tpd(placements, ml_dtypes.bfloat16)
        i = np.unravel_index(np.argmin(tpd), tpd.shape)
        return {"placements": placements, "tpd": tpd, "best": placements[i],
                "best_tpd": float(tpd[i]), "scored_min": float(tpd[i]),
                "scored_best": placements[i], "swarm_still": still}

    def _tpd(self, placements, dtype=np.float64) -> np.ndarray:
        K, P, D = placements.shape
        cfg = self.cfg
        out = self.ref.tpd(placements.reshape(K * P, D), self.pool,
                           cfg["depth"], cfg["width"],
                           cfg["memory_penalty"], dtype)
        return np.asarray(out, np.float64).reshape(K, P)

    def compare(self, got: dict) -> dict:
        placements = got["placements"]
        K, P, D = placements.shape
        valid = tree.valid_rows(placements.reshape(K * P, D),
                                self.cfg["clients"]).reshape(K, P)
        want = self._tpd(placements)
        gaps = np.where(valid, np.abs(got["tpd"] - want) / want, np.inf)
        best = np.asarray(got["best"], np.int64)[None, None]
        best_ref = float(self._tpd(best)[0, 0])
        best_valid = bool(tree.valid_rows(best[0], self.cfg["clients"])[0])
        return {"numbers": {
            "tpd_gap": float(np.max(gaps)),
            "gbest_gap": abs(got["best_tpd"] - best_ref) / best_ref
            if best_valid else float("inf"),
            # the best placement found is no worse than any scored one
            "gbest_excess": max(0.0, (best_ref - float(want.min()))
                                / float(want.min())),
            # the search returns the best that it scored, exactly
            "gbest_vs_scored": abs(got["best_tpd"] - got["scored_min"])
            / got["scored_min"],
            "gbest_slots_off": float(np.sum(best[0, 0] !=
                                            got["scored_best"])),
            "swarm_still": got["swarm_still"],
            "invalid_placements": float(np.sum(~valid) + (not best_valid))},
            "answers": gaps.ravel(), "answer_limit": "tpd_gap"}
