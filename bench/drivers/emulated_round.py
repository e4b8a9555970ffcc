"""Closed loop of emulated federated rounds.

The program's own sequential loop, as ``repro.experiments.runner.
run_single`` drives it: ``strategy.propose`` -> ``EmulatedEnvironment.
step`` (one ``FederatedOrchestrator.run_round``: input assembly, local
SGD on every client, the per-level merge, a 512-sample evaluation) ->
``strategy.observe``. The next round starts when the last one returns.

Set-up builds the environment from inputs made from the seed (pool,
data, shards, weights), warms every batch shape, and runs the first
``checked_rounds`` rounds through that same loop, keeping their losses
and weights for the comparison. The window then continues the same
objects until it closes; the global weights that the window's last
round started from and those it ended with are kept too, so that the
reference can run that round from the same start.
"""
from __future__ import annotations

import time

import numpy as np

from harness import gen, tree


class _Samples:
    """The classification set as the program's dataset reads it."""

    def __init__(self, features, labels):
        self.features, self.labels = features, labels

    def __len__(self):
        return len(self.labels)


class Cell:
    unit = "rounds"

    def __init__(self, cfg, traffic, seed, ref, spans):
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.ref, self.spans = ref, spans
        self.placements, self.tpds, self.losses = [], [], []
        self.round_times = []

    # ------------------------------------------------------------------
    def setup(self) -> None:
        import jax
        from repro.configs import get_config
        from repro.core.hierarchy import ClientPool, Hierarchy
        from repro.core.registry import create_strategy
        from repro.data.synthetic import FederatedDataset
        from repro.experiments.environments import EmulatedEnvironment
        from repro.fl.orchestrator import FederatedOrchestrator
        from repro.models import get_model

        cfg, tr, seed = self.cfg, self.traffic, self.seed
        n = tr["clients"]
        self.pool = gen.pool(tr["pool"], n, gen.stream(seed, gen.POOL))
        self.features, self.labels = gen.classification(
            cfg["data"], cfg["in_features"], cfg["classes"],
            gen.stream(seed, gen.DATA))
        self.shards = gen.dirichlet_split(
            self.labels, n, cfg["data"]["alpha"],
            cfg["data"]["min_per_client"], gen.stream(seed, gen.SPLIT))
        hierarchy = Hierarchy(depth=tr["depth"], width=tr["width"],
                              trainers_per_leaf=tr["trainers_per_leaf"],
                              n_clients=n)
        if hierarchy.total_clients != n:
            raise ValueError(f"the tree holds {hierarchy.total_clients} "
                             f"clients, the traffic names {n}")
        model = get_model(get_config(cfg["program_model"]))
        data = FederatedDataset(
            base=_Samples(self.features, self.labels),
            partitions=[s.copy() for s in self.shards],
            alpha=cfg["data"]["alpha"])
        orch = FederatedOrchestrator(
            model, hierarchy,
            ClientPool(**{k: v.copy() for k, v in self.pool.items()}),
            data, local_lr=cfg["local_lr"], local_steps=tr["local_steps"],
            batch_size=tr["batch_size"], time_scale=cfg["time_scale"],
            comm_latency=tr["comm_latency"], seed=gen.seed31(seed),
            timing="deterministic", engine="batched")
        init = gen.mlp_weights(cfg, seed)
        params = {"layers": [{"w": w, "b": b} for w, b in init]}
        want = jax.eval_shape(model.init, jax.random.key(0))
        if jax.tree.structure(params) != jax.tree.structure(want) or any(
                a.shape != b.shape or a.dtype != b.dtype for a, b in zip(
                    jax.tree.leaves(params), jax.tree.leaves(want),
                    strict=True)):
            raise ValueError("the program's MLP parameters no longer have "
                             "the layout [{'w', 'b'}] per layer")
        orch.set_global(params)
        self.init = [(np.asarray(w), np.asarray(b)) for w, b in init]
        self.n_params = sum(a.size for wb in self.init for a in wb)
        self.env = EmulatedEnvironment(orch)
        self.strategy = create_strategy(
            tr["strategy"], hierarchy, seed=gen.seed31(seed, gen.PROGRAM, 1),
            clients=orch.clients, cost_model=self.env.cost_model)
        self.orch = orch
        self.env.begin()

        self.checked_params = []
        for r in range(tr["checked_rounds"]):
            self._round(r)
            self.checked_params.append(_host(orch.params))
        self.round_times.clear()

    def _round(self, r: int) -> None:
        t0 = time.perf_counter()
        self.last = (r, self.orch.params)   # a reference, not a copy
        with self.spans("propose"):
            placement = np.asarray(self.strategy.propose(r), np.int64)
        with self.spans("step"):
            obs = self.env.step(r, placement)
        with self.spans("observe"):
            self.strategy.observe(placement, obs.tpd)
        self.round_times.append(time.perf_counter() - t0)
        self.placements.append(placement)
        self.tpds.append(float(obs.tpd))
        self.losses.append(float(obs.metrics["loss"]))

    # ------------------------------------------------------------------
    def window(self, seconds: float) -> dict:
        r = len(self.tpds)
        t0 = time.perf_counter()
        while True:
            self._round(r)
            r += 1
            elapsed = time.perf_counter() - t0
            if elapsed >= seconds:
                break
        n = len(self.round_times)
        return {"elapsed": elapsed, "units": n,
                "round_s": elapsed / n,
                "round_p95_s": float(np.percentile(self.round_times, 95))}

    def facts(self) -> dict:
        """Counts the per-layer metrics read: model FLOPs per round."""
        from harness import work
        tr = self.traffic
        trained = tr["local_steps"] * sum(
            min(tr["batch_size"], len(s)) for s in self.shards)
        evaluated = min(self.cfg["eval_samples"], len(self.labels))
        return {"flops_per_unit": work.mlp_round_flops(
            self.n_params, trained, evaluated)}

    def release(self) -> None:
        r, before = self.last
        self.last_round = r
        self.last_before = _host(before)
        self.last_after = _host(self.orch.params)
        self.env = self.strategy = self.orch = self.last = None

    # ------------------------------------------------------------------
    def outputs(self, who: str) -> dict:
        """What the comparison reads: the program's, or the control's
        (the reference in the precision below the configuration's)."""
        k = self.traffic["checked_rounds"]
        placements = np.stack(self.placements)
        if who == "program":
            return {"loss": self.losses[:k], "params": self.checked_params,
                    "last_loss": self.losses[self.last_round],
                    "last_params": self.last_after,
                    "tpd": np.asarray(self.tpds), "placements": placements}
        if who == "half_batch":
            # a fault read in the reference put in the program's place:
            # the merge leaves out the second half of the clients and
            # takes the weighted mean over the rest
            run, last = self._train("float32", half=True)
            tpd = np.asarray(self.tpds)
        else:
            run, last = self._train("bfloat16")
            tpd = self.ref.round_tpd(placements, self.pool, self.traffic,
                                     self.cfg, np.float32).astype(np.float64)
        return {"loss": run["loss"], "params": run["params"],
                "last_loss": last["loss"][0], "last_params": last["params"][0],
                "tpd": tpd, "placements": placements}

    def _train(self, dtype: str, half: bool = False) -> tuple:
        """The reference's checked rounds from the initial weights, and
        its run of the window's last round from where the program's
        started."""
        import jax

        def rounds(init, first, n):
            return self.ref.train_rounds(
                self.cfg, self.traffic, init, self.features, self.labels,
                self.shards, n, dtype, first_round=first,
                clients=len(self.shards) // 2 if half else None)
        with jax.default_matmul_precision("highest"):
            return (rounds(self.init, 0, self.traffic["checked_rounds"]),
                    rounds(self.last_before, self.last_round, 1))

    def compare(self, got: dict) -> dict:
        """The numbers compared, each as read (the limits live in
        ``bench/limits``), and every round's own TPD gap (``answers``,
        held to the ``tpd_gap`` limit)."""
        want, last = self._train("float32")
        placements = got["placements"]
        valid = tree.valid_rows(placements, self.traffic["clients"])
        ref_tpd = self.ref.round_tpd(placements, self.pool, self.traffic,
                                     self.cfg)
        gaps = np.where(valid, np.abs(got["tpd"] - ref_tpd) /
                        np.abs(ref_tpd), np.inf)
        loss = max(abs(a - b) / abs(b)
                   for a, b in zip(got["loss"], want["loss"], strict=True))
        moved = _moved_leaves(self.init, want["params"][0])
        last_moved = _moved_leaves(self.last_before, last["params"][0])
        return {"numbers": {
            "loss_gap": float(loss),
            "last_loss_gap": abs(got["last_loss"] - last["loss"][0])
            / abs(last["loss"][0]),
            "last_update_gap": _worst_leaf_gap(
                self.last_before, got["last_params"], last["params"][0],
                last_moved),
            "update1_gap": _worst_leaf_gap(self.init, got["params"][0],
                                           want["params"][0], moved),
            f"update{len(want['params'])}_gap": _worst_leaf_gap(
                self.init, got["params"][-1], want["params"][-1], moved),
            "tpd_gap": float(np.max(gaps)),
            "invalid_placements": float(np.sum(~valid))},
            "answers": gaps, "answer_limit": "tpd_gap"}


def _host(params) -> list:
    """The program's global weights as [(w, b)] numpy arrays."""
    return [(np.asarray(layer["w"]), np.asarray(layer["b"]))
            for layer in params["layers"]]


def _leaves(params) -> list:
    return [a for wb in params for a in wb]


def _moved_leaves(init, ref_after) -> list:
    """Leaves the reference moves: the norm of a leaf's first update is at
    least a thousandth of the median leaf's. Leaves under it move by
    round-off alone and are left out."""
    norms = np.asarray([np.linalg.norm(b.astype(np.float64) - a)
                        for a, b in zip(_leaves(init), _leaves(ref_after),
                                        strict=True)])
    return list(np.nonzero(norms >= 1e-3 * np.median(norms))[0])


def _worst_leaf_gap(init, got, want, leaves) -> float:
    """Worst leaf of |‖got - init‖ - ‖want - init‖| over max(‖want -
    init‖ of that leaf, of the median leaf)."""
    g = [np.linalg.norm(b.astype(np.float64) - a)
         for a, b in zip(_leaves(init), _leaves(got), strict=True)]
    w = [np.linalg.norm(b.astype(np.float64) - a)
         for a, b in zip(_leaves(init), _leaves(want), strict=True)]
    med = float(np.median(w))
    return float(max(abs(g[i] - w[i]) / max(w[i], med) for i in leaves))
