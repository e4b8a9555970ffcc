"""Closed loop of emulated federated rounds of a client language model.

The program's own sequential loop, as ``repro.experiments.runner.
run_single`` drives it: ``strategy.propose`` -> ``EmulatedEnvironment.
step`` (one ``FederatedOrchestrator.run_round``: every silo's local SGD
steps, the per-level merge, the loss on one evaluation sequence) ->
``strategy.observe``. The next round starts when the last one returns.

The model is the configuration's ``program_model`` cut as the file says
(``num_hidden_layers``, the ``n_routed_experts`` held on this chip of
``router_experts``, the ``vocab_size`` slice); set-up refuses to run if
any other width of the program's model differs from the file. Token
sequences come from the seed (:class:`Tokens`), weights too. Set-up
warms every shape and runs the first ``checked_rounds`` rounds through
the window's loop, keeping their losses and, per weight leaf, the norm
of the change from the initial weights (the weights themselves are too
large to keep many copies of). The window then continues; the weights
its last round started from are kept on the host, so that the reference
can run that round from the same start.
"""
from __future__ import annotations

import dataclasses
import gc
import time

import numpy as np

from harness import gen, tree

# the HF config keys that fix the program's model, beside the program's
# own value for each (the first three are the cut, listed in "reduced")
WIDTHS = {
    "num_hidden_layers": lambda m: m.n_layers,
    "n_routed_experts": lambda m: m.moe.held,
    "vocab_size": lambda m: m.vocab_size,
    "first_k_dense_replace": lambda m: m.first_k_dense,
    "hidden_size": lambda m: m.d_model,
    "intermediate_size": lambda m: m.d_ff,
    "num_attention_heads": lambda m: m.n_heads,
    "num_key_value_heads": lambda m: m.n_kv_heads,
    "kv_lora_rank": lambda m: m.mla.kv_lora_rank,
    "qk_nope_head_dim": lambda m: m.mla.qk_nope_head_dim,
    "qk_rope_head_dim": lambda m: m.mla.qk_rope_head_dim,
    "v_head_dim": lambda m: m.mla.v_head_dim,
    "moe_intermediate_size": lambda m: m.moe.d_ff_expert,
    "n_shared_experts": lambda m: m.moe.n_shared_experts,
    "num_experts_per_tok": lambda m: m.moe.top_k,
    # the dropless path's routing: the top-k weights neither
    # renormalized nor scaled
    "norm_topk_prob": lambda m: m.moe.capacity_factor is not None,
    "routed_scaling_factor": lambda m: 1,
    "router_experts": lambda m: m.moe.n_experts,
    "expert_shard": lambda m: m.moe.expert_shard,
    "rms_norm_eps": lambda m: m.norm_eps,
    "rope_theta": lambda m: m.rope_theta,
    "tie_word_embeddings": lambda m: m.tie_embeddings,
    "aux_loss_alpha": lambda m: m.moe.router_aux_weight,
    "seq_aux": lambda m: m.moe.capacity_factor is None,
}
YARN = {"factor": "factor", "original_max_position_embeddings":
        "original_max_position", "beta_fast": "beta_fast",
        "beta_slow": "beta_slow", "mscale": "mscale",
        "mscale_all_dim": "mscale_all_dim"}


def program_model(cfg: dict, traffic: dict):
    """The program's model config as this cell runs it, and the
    reference's configuration (the file's, or at the rehearsal's reduced
    size the program's widths under the file's keys)."""
    from repro.configs import get_config
    base = get_config(cfg["program_model"])
    if traffic.get("model") == "reduced":
        # the CPU rehearsal: tiny widths, float32 activations (at these
        # sizes the comparison's limits are not the chip's)
        m = base.reduced()
        m = m.replace(dtype="float32", moe=dataclasses.replace(
            m.moe, n_experts=8, top_k=3, n_held=4, expert_shard=0))
        ref_cfg = dict(cfg, **{k: f(m) for k, f in WIDTHS.items()})
        return m, ref_cfg
    m = base.replace(
        n_layers=cfg["num_hidden_layers"], vocab_size=cfg["vocab_size"],
        moe=dataclasses.replace(base.moe, n_held=cfg["n_routed_experts"],
                                expert_shard=cfg["expert_shard"]))
    bad = {k: (cfg[k], f(m)) for k, f in WIDTHS.items() if cfg[k] != f(m)}
    bad.update({k: (cfg["rope_scaling"][k], getattr(m.rope_scaling, a))
                for k, a in YARN.items()
                if cfg["rope_scaling"][k] != getattr(m.rope_scaling, a)})
    if bad:
        raise ValueError(f"the program's model differs from the "
                         f"configuration file (file, program): {bad}")
    return m, cfg


class Tokens:
    """Token sequences over the vocabulary slice, made from the seed.

    Each silo's stream is the affine recurrence of the program's
    ``FederatedLMDataset``, ``t[i] = (t0 * a^(i mod 13) + b * i) mod V``,
    with per-sequence ``a`` in 1..7, ``t0`` uniform and ``b`` drawn from
    the silo's own eighth of the ids (non-IID). The evaluation sequence
    has a stream of its own."""

    def __init__(self, seed: int, n_clients: int, vocab: int, seq_len: int):
        self.seed, self.n_clients = seed, n_clients
        self.vocab, self.seq_len = vocab, seq_len

    def _seq(self, rng, lo: int, hi: int, n: int) -> dict:
        a = rng.integers(1, 8, size=(n, 1))
        b = rng.integers(lo, hi, size=(n, 1))
        t0 = rng.integers(0, self.vocab, size=(n, 1))
        i = np.arange(self.seq_len + 1)[None, :]
        toks = ((t0 * np.power(a, i % 13) + b * i) % self.vocab
                ).astype(np.int32)
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}

    def client_batch(self, client: int, batch_size: int, step: int) -> dict:
        rng = gen.stream(self.seed, gen.DATA, client, step)
        part = self.vocab // self.n_clients
        return self._seq(rng, client * part, (client + 1) * part, batch_size)

    def eval_batch(self, n: int = 1) -> dict:
        return self._seq(gen.stream(self.seed, gen.SAMPLE), 0, self.vocab, 1)

    def client_weights(self) -> np.ndarray:
        return np.full(self.n_clients, 1.0 / self.n_clients, np.float32)


def init_weights(model, seed: int):
    """The model's weights made from the seed on the default device in
    one jitted call, in the program's layout: norm scales 1, the
    embedding N(0, 0.02^2), every matrix N(0, 1/fan_in), float32."""
    import jax
    import jax.numpy as jnp

    shapes = jax.eval_shape(model.init, jax.random.key(0))
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(shapes)[0]]
    leaves, treedef = jax.tree.flatten(shapes)

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for k, path, x in zip(keys, paths, leaves, strict=True):
            if "scale" in path:
                out.append(jnp.ones(x.shape, jnp.float32))
                continue
            std = 0.02 if "embed" in path else 1.0 / np.sqrt(x.shape[-2])
            out.append(jax.random.normal(k, x.shape, jnp.float32) * std)
        return jax.tree.unflatten(treedef, out)

    return make(jax.random.key(gen.seed31(seed, gen.WEIGHTS)))


def _host_leaves(params) -> list:
    import jax
    return [np.asarray(x) for x in jax.tree.leaves(params)]


class Cell:
    unit = "rounds"

    def __init__(self, cfg, traffic, seed, ref, spans):
        self.traffic, self.seed = traffic, seed
        self.ref, self.spans = ref, spans
        self.model_cfg, self.cfg = program_model(cfg, traffic)
        self.placements, self.tpds, self.losses = [], [], []
        self.round_times = []
        self._want = None

    # ------------------------------------------------------------------
    def setup(self) -> None:
        from repro.core.hierarchy import ClientPool, Hierarchy
        from repro.core.registry import create_strategy
        from repro.experiments.environments import EmulatedEnvironment
        from repro.fl.orchestrator import FederatedOrchestrator
        from repro.models import get_model

        cfg, tr, seed = self.cfg, self.traffic, self.seed
        n = tr["clients"]
        self.pool = gen.pool(tr["pool"], n, gen.stream(seed, gen.POOL))
        self.data = Tokens(seed, n, cfg["vocab_size"], tr["seq_len"])
        hierarchy = Hierarchy(depth=tr["depth"], width=tr["width"],
                              trainers_per_leaf=tr["trainers_per_leaf"],
                              n_clients=n)
        if hierarchy.total_clients != n:
            raise ValueError(f"the tree holds {hierarchy.total_clients} "
                             f"clients, the traffic names {n}")
        model = get_model(self.model_cfg)
        orch = FederatedOrchestrator(
            model, hierarchy,
            ClientPool(**{k: v.copy() for k, v in self.pool.items()}),
            self.data, local_lr=cfg["local_lr"],
            local_steps=tr["local_steps"], batch_size=tr["batch_size"],
            time_scale=cfg["time_scale"], comm_latency=tr["comm_latency"],
            seed=gen.seed31(seed), timing="deterministic", engine="batched")
        orch.set_global(init_weights(model, seed))
        self.init = _host_leaves(orch.params)
        self.n_params = sum(a.size for a in self.init)
        self.env = EmulatedEnvironment(orch)
        self.strategy = create_strategy(
            tr["strategy"], hierarchy, seed=gen.seed31(seed, gen.PROGRAM, 1),
            clients=orch.clients, cost_model=self.env.cost_model)
        self.orch = orch
        self.env.begin()

        self.checked_norms = []
        for r in range(tr["checked_rounds"]):
            self._round(r)
            self.checked_norms.append(self.ref.leaf_norms(
                self.init, _host_leaves(orch.params)))
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
        return {"elapsed": elapsed, "units": n, "round_s": elapsed / n}

    def facts(self) -> dict:
        """Counts the per-layer metrics read, per round: model FLOPs, and
        the held experts' operations and bytes (``harness.moe_work``),
        from the held experts' token-expert pairs that the program
        counted in the window (a traced run), else their expectation
        under uniform routing."""
        from harness import moe_work
        from repro.utils import tracing
        m, tr = self.model_cfg, self.traffic
        rounds = max(len(self.round_times), 1)
        seqs = tr["clients"] * tr["local_steps"] * tr["batch_size"]
        tokens = seqs * tr["seq_len"]
        per_token = m.moe.top_k * m.moe.held / m.moe.n_experts \
            * (m.n_layers - m.first_k_dense)
        counters = tracing.snapshot()["counters"]
        trained = counters.get("moe.held_assignments")
        evaluated = counters.get("moe.eval_held_assignments")
        trained = trained / rounds if trained else per_token * tokens
        evaluated = evaluated / rounds if evaluated \
            else per_token * tr["seq_len"]
        shape = moe_work.Shape.of(m)
        ops, nbytes = moe_work.expert_work(
            shape, trained, evaluated,
            calls=seqs * shape.moe_layers, eval_calls=shape.moe_layers)
        return {"flops_per_unit": moe_work.round_flops(
                    shape, tokens, seqs, trained, tr["seq_len"], evaluated),
                "expert_ops_per_unit": ops,
                "expert_bytes_per_unit": nbytes}

    def release(self) -> None:
        import jax
        r, before = self.last
        self.last_round = r
        self.last_before = _host_leaves(before)
        self.last_norms = self.ref.leaf_norms(
            self.last_before, _host_leaves(self.orch.params))
        self.treedef = jax.tree.structure(self.orch.params)
        self.env = self.strategy = self.orch = self.last = None
        gc.collect()

    # ------------------------------------------------------------------
    def outputs(self, who: str) -> dict:
        """What the comparison reads: the program's, the control's (the
        reference in bfloat16, below the configuration's float32), or a
        fault's read in the reference: ``half_batch`` merges only the
        first half of the silos, ``drop_expert`` leaves held expert 0's
        output out."""
        k = self.traffic["checked_rounds"]
        placements = np.stack(self.placements)
        if who == "program":
            return {"loss": self.losses[:k], "norms": self.checked_norms,
                    "last_loss": self.losses[self.last_round],
                    "last_norms": self.last_norms,
                    "tpd": np.asarray(self.tpds), "placements": placements}
        kw = {"control": {"dtype": "bfloat16"},
              "half_batch": {"clients": self.traffic["clients"] // 2},
              "drop_expert": {"drop_expert": 0}}[who]
        run, last = self._train(**kw)
        tpd = np.asarray(self.tpds) if who != "control" else \
            self.ref.round_tpd(placements, self.pool, self.traffic,
                               self.cfg, np.float32).astype(np.float64)
        return {"loss": run["loss"], "norms": run["norms"],
                "last_loss": last["loss"][0], "last_norms": last["norms"][0],
                "tpd": tpd, "placements": placements}

    def _train(self, **kw) -> tuple:
        """The reference's checked rounds from the initial weights, and
        its run of the window's last round from where the program's
        started (its norms then relative to that start)."""
        import jax

        def rounds(init, first, n):
            tree_ = jax.tree.unflatten(self.treedef, init)
            return self.ref.train_rounds(self.cfg, self.traffic, tree_,
                                         self.data, n, first_round=first,
                                         **kw)
        with jax.default_matmul_precision("highest"):
            return (rounds(self.init, 0, self.traffic["checked_rounds"]),
                    rounds(self.last_before, self.last_round, 1))

    def compare(self, got: dict) -> dict:
        """The numbers compared, each as read (the limits live in
        ``bench/limits``), and every round's own TPD gap (``answers``,
        held to the ``tpd_gap`` limit)."""
        if self._want is None:
            self._want = self._train()
        want, last = self._want
        placements = got["placements"]
        valid = tree.valid_rows(placements, self.traffic["clients"])
        ref_tpd = self.ref.round_tpd(placements, self.pool, self.traffic,
                                     self.cfg)
        gaps = np.where(valid, np.abs(got["tpd"] - ref_tpd) /
                        np.abs(ref_tpd), np.inf)
        loss = max(abs(a - b) / abs(b)
                   for a, b in zip(got["loss"], want["loss"], strict=True))
        moved = _moved(want["norms"][0])
        k = len(want["norms"])
        return {"numbers": {
            "loss_gap": float(loss),
            "last_loss_gap": abs(got["last_loss"] - last["loss"][0])
            / abs(last["loss"][0]),
            "last_update_gap": _worst_gap(got["last_norms"],
                                          last["norms"][0],
                                          _moved(last["norms"][0])),
            "update1_gap": _worst_gap(got["norms"][0], want["norms"][0],
                                      moved),
            f"update{k}_gap": _worst_gap(got["norms"][-1], want["norms"][-1],
                                         moved),
            "tpd_gap": float(np.max(gaps)),
            "invalid_placements": float(np.sum(~valid))},
            "answers": gaps, "answer_limit": "tpd_gap"}


def _moved(norms: dict) -> dict:
    """Per group of norms (``ref.leaf_norms``), the entries the reference
    moves: at least a thousandth of the group's median; the rest move by
    round-off alone and are left out."""
    return {g: np.nonzero(v >= 1e-3 * np.median(v))[0]
            for g, v in norms.items() if len(v)}


def _worst_gap(got: dict, want: dict, moved: dict) -> float:
    """Worst entry of |got - want| / max(want, its group's median want),
    over norms of the change from the round's start: the leaves against
    the median leaf, each held expert's matrices against the median
    expert's."""
    worst = 0.0
    for g, idx in moved.items():
        a, b = np.asarray(got[g]), np.asarray(want[g])
        med = float(np.median(b))
        worst = max(worst, *(abs(a[i] - b[i]) / max(b[i], med)
                             for i in idx))
    return float(worst)
