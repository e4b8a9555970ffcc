"""The SDFL coordinator: federated rounds with black-box TPD measurement.

This is the single-host emulation of the paper's docker/MQTT deployment
(Sec. IV-C): N heterogeneous clients train a real model (the paper's
1.8M-param MLP by default) on non-IID partitions; every round a
placement strategy proposes the aggregation tree; aggregation is
actually computed cluster-by-cluster with per-cluster timing; the
round's Total Processing Delay composes the per-cluster times exactly
like the physical system would experience them:

    TPD = max_c (local train time) + sum_levels max_cluster (agg time)

Heterogeneity: each client's measured compute time is scaled by
1/pspeed_c — the emulation analogue of the paper's docker cpu/memory
limits. The coordinator never reads pspeed to *decide* anything: the
strategy only ever sees the final TPD (black-box, as in the paper).

Two round engines drive the same semantics:

* ``engine='batched'`` (default): client params ride a leading ``C``
  dim; local training is ONE jit'd ``vmap``-of-``scan`` per round (per
  batch-shape bucket) and aggregation is ONE jit'd weighted
  ``segment_sum`` per tree level, driven by ``Hierarchy.round_plan``
  index tables. This is what scales the emulation past a few dozen
  clients (benchmarks/bench_round_engine.py sweeps 16 -> 256).
  When the device cannot hold every client's training state at once (a
  client model at published widths), the same engine trains the clients
  in chunks, cluster by cluster down the tree, and folds each chunk's
  weighted updates into one accumulator per level as it goes
  (:meth:`FederatedOrchestrator._round_chunked`); the chunk size comes
  from the model's bytes and the device's memory.
* ``engine='loop'``: the original per-client / per-cluster dispatch.
  Its wall-clock timing is per-cluster-faithful (the docker-faithful
  'measured' mode on a quiet box); the batched engine necessarily
  *attributes* measured wall time across clients/clusters by load share
  instead. Deterministic timing is identical between engines.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.hierarchy import ClientPool, Hierarchy, TopologyUpdate, slot_remap
from repro.core.placement import PlacementStrategy
from repro.data.synthetic import FederatedDataset
from repro.faults.tolerance import quorum_count, quorum_merge_batched
from repro.fl.aggregation import SegmentAggregator
from repro.fl.distributed import elastic_rehierarchize
from repro.models.api import Model
from repro.utils import tracing
from repro.utils.trees import tree_weighted_sum

# rng stream tag for elastic data provisioning: joiner shards draw from
# a dedicated stream so admitting clients never perturbs the training /
# noise rng sequences of the surviving population
_ELASTIC_STREAM = 0xE1A57


@dataclass
class RoundRecord:
    round_idx: int
    placement: list
    tpd: float
    train_time: float
    agg_time: float
    loss: float
    accuracy: float


@dataclass
class FederatedRunResult:
    strategy: str
    rounds: List[RoundRecord] = field(default_factory=list)

    @property
    def tpds(self) -> np.ndarray:
        return np.asarray([r.tpd for r in self.rounds])

    @property
    def total_processing_time(self) -> float:
        return float(self.tpds.sum())

    def summary(self) -> dict:
        if not self.rounds:  # zero rounds: well-defined empties, no NaN
            return {"strategy": self.strategy, "rounds": 0,
                    "total_tpd": 0.0, "mean_tpd": 0.0,
                    "last10_mean_tpd": 0.0, "final_accuracy": 0.0}
        return {
            "strategy": self.strategy,
            "rounds": len(self.rounds),
            "total_tpd": self.total_processing_time,
            "mean_tpd": float(self.tpds.mean()),
            "last10_mean_tpd": float(self.tpds[-10:].mean()),
            "final_accuracy": self.rounds[-1].accuracy,
        }


class FederatedOrchestrator:
    """Runs FL rounds against a strategy, measuring black-box TPD.

    The training population is ELASTIC: :meth:`admit` / :meth:`retire`
    resize the live run mid-flight (joiners train from the current
    global model and get fresh data shards; survivors keep theirs), and
    :meth:`sync_population` reconciles hierarchy/data/engine state after
    event-driven pool resizes — see the elastic section below."""

    def __init__(self, model: Model, hierarchy: Hierarchy,
                 clients: ClientPool, data: FederatedDataset, *,
                 local_lr: float = 0.05, local_steps: int = 4,
                 batch_size: int = 32, time_scale: float = 1.0,
                 comm_latency: float = 0.0, seed: int = 0,
                 rng_noise: float = 0.0, timing: str = "measured",
                 engine: str = "auto"):
        """``timing``: 'measured' uses wall-clock (the docker-faithful
        mode — requires a quiet machine); 'deterministic' charges eq.6
        unit-work/pspeed delays through the SAME black-box interface
        (reproducible on loaded CI boxes). Training math is identical.

        ``engine``: 'batched' (vmap'd clients + segment-sum levels),
        'loop' (per-client dispatch), or 'auto' (batched)."""
        assert len(clients) == hierarchy.total_clients == data.n_clients
        self.model = model
        self.hierarchy = hierarchy
        self.clients = clients
        self.data = data
        self.local_steps = local_steps
        self.batch_size = batch_size
        self.time_scale = time_scale
        self.comm_latency = comm_latency
        self.rng = np.random.default_rng(seed)
        self.rng_noise = rng_noise
        assert timing in ("measured", "deterministic")
        self.timing = timing
        assert engine in ("auto", "loop", "batched")
        self.engine = "batched" if engine == "auto" else engine

        self.params = model.init(jax.random.key(seed))
        self.local_lr = local_lr
        self._grad_step = jax.jit(jax.value_and_grad(
            lambda p, b: model.loss_fn(p, b)[0]), static_argnames=())
        self._eval = jax.jit(lambda p, b: model.loss_fn(p, b),
                             static_argnames=())
        self.weights = data.client_weights()

        # weighted-sum of a cluster's updates, jit'd once (loop engine)
        self._wsum = jax.jit(
            lambda trees, w: tree_weighted_sum(trees, w),
            static_argnames=())

        # batched engine state (built lazily in _warmup)
        self._agg: Optional[SegmentAggregator] = None
        self._local_fns: Dict[bool, Callable] = {}
        self._folds: Optional[tuple] = None
        self._chunk_for: Optional[tuple] = None   # (tree, chunk)
        # a model whose loss does not batch over clients trains them one
        # after another; local_all then also returns the token-expert
        # pairs its held experts computed (``moe_held``), if it has any
        self._per_client = not getattr(model, "batches_over_clients", True)
        # the sample set's device copy, (host features, host labels,
        # device features, device labels), and evaluation slices of it
        self._samples: Optional[tuple] = None
        self._eval_batches: Dict[int, dict] = {}

        # elastic population state: the hierarchy is a versioned run
        # property (mirrors SimulatedEnvironment); the capacity window
        # honors a deliberately overstuffed construction-time population
        self.topology_version = 0
        self._capacity = max(hierarchy.max_clients, len(clients))
        self._elastic_rng = np.random.default_rng((seed, _ELASTIC_STREAM))

        # trace recording (repro.calibration): when enabled, each round
        # captures per-client train times and per-level/per-cluster
        # aggregation delays into ``last_timings``. Recording reads
        # values the engines already computed — no extra rng draws, no
        # numeric changes — so recording=off runs are byte-identical.
        self.record_timings = False
        self.last_timings: Optional[dict] = None
        self._trace: Optional[dict] = None

    # ==================================================================
    # deterministic per-cluster delay (eq. 6), shared by both engines
    # ==================================================================
    # eq. 6 payload units / this = charged delay units: puts aggregation
    # in the paper's regime — the 30 MB JSON model on a 64 MB container
    # dominated the 20-30 s docker rounds, and placement moves exactly
    # this term
    EQ6_PAYLOAD_SCALE = 10.0

    def _det_cluster_work(self, member_clients: Sequence[int]) -> float:
        """eq. 6 payload units: own + ACTUAL children model payloads."""
        mds = self.clients.mdatasize
        return float(sum(mds[int(c)] for c in member_clients)) \
            / self.EQ6_PAYLOAD_SCALE

    def _cluster_time(self, host: int, dt: float, n_parts: int) -> float:
        """Emulated heterogeneity + comm hops + optional noise."""
        t = dt / self.clients.pspeed[host] + self.comm_latency * n_parts
        if self.rng_noise:
            t *= 1.0 + self.rng.normal(0, self.rng_noise)
        return t

    # ==================================================================
    # loop engine (original per-client / per-cluster dispatch)
    # ==================================================================
    def _local_train(self, client_id: int, round_idx: int):
        """Client's local steps. Returns (new_params, loss, measured_time)."""
        params = self.params
        t0 = time.perf_counter()
        loss = 0.0
        for s in range(self.local_steps):
            batch = self.data.client_batch(client_id, self.batch_size,
                                           round_idx * self.local_steps + s)
            lval, grads = self._grad_step(params, batch)
            params = jax.tree.map(
                lambda p, g: p - self.local_lr * g, params, grads)
            loss = float(lval)
        jax.block_until_ready(jax.tree.leaves(params)[0])
        if self.timing == "deterministic":
            dt = float(self.local_steps)  # unit work per local step
        else:
            dt = time.perf_counter() - t0
        return params, loss, dt / self.clients.pspeed[client_id]

    def _aggregate(self, updates: List, placement: np.ndarray):
        """Cluster-by-cluster aggregation with per-cluster timing.

        Returns (global_params, total_agg_time) where total_agg_time =
        sum over levels of the level's max cluster time (eq. 7 semantics,
        with per-cluster times instead of the model's estimate).
        """
        h = self.hierarchy
        weighted = [jax.tree.map(lambda x, w=w: x * w, u)
                    for u, w in zip(updates, self.weights, strict=True)]
        trainers = h.trainer_assignment(placement)
        slot_value = [None] * h.dimensions
        mds = self.clients.mdatasize
        total = 0.0
        for level in range(h.depth - 1, -1, -1):
            level_max = 0.0
            row = None
            if self._trace is not None:
                row = {"level": level, "slots": [], "hosts": [],
                       "loads": [], "n_parts": [], "delays": []}
            for s in range(h.level_starts[level], h.level_starts[level + 1]):
                host = int(placement[s])
                parts = [weighted[host]]
                members = [host]
                kids = h.children_slots(s)
                if kids:
                    parts.extend(slot_value[k] for k in kids)
                    members.extend(int(placement[k]) for k in kids)
                else:
                    li = s - h.level_starts[h.depth - 1]
                    parts.extend(weighted[t] for t in trainers[li])
                    members.extend(trainers[li])
                t0 = time.perf_counter()
                acc = self._wsum(parts, [1.0] * len(parts))
                jax.block_until_ready(jax.tree.leaves(acc)[0])
                if self.timing == "deterministic":
                    dt = self._det_cluster_work(members)
                else:
                    dt = time.perf_counter() - t0
                slot_value[s] = acc
                cluster_t = self._cluster_time(host, dt, len(parts))
                if row is not None:
                    row["slots"].append(s)
                    row["hosts"].append(host)
                    row["loads"].append(
                        float(sum(mds[int(c)] for c in members)))
                    row["n_parts"].append(len(parts))
                    row["delays"].append(float(cluster_t))
                level_max = max(level_max, cluster_t)
            if row is not None:
                self._trace["levels"].append(row)
            total += level_max
        return slot_value[0], total

    def _round_loop(self, r: int, placement: np.ndarray):
        updates, train_times = [], []
        for c in range(self.hierarchy.total_clients):
            p, _, t = self._local_train(c, r)
            updates.append(p)
            train_times.append(t)
        if self._trace is not None:
            self._trace["train"] = {
                "clients": list(range(self.hierarchy.total_clients)),
                "times": [float(t) for t in train_times]}
        new_params, agg_time = self._aggregate(updates, placement)
        return new_params, max(train_times), agg_time

    # ==================================================================
    # batched engine: vmap'd local steps + per-level segment sums
    # ==================================================================
    def _device_samples(self):
        """The dataset's fixed sample set on the device, as ``(features,
        labels)``, when its batches are positions into that set
        (``client_indices`` over ``base.features`` / ``base.labels``);
        ``None`` for a dataset that makes its batches step by step.

        Uploaded on first use (the warm-up) and again only when the base
        arrays themselves change: elastic joiners' shards index into the
        same set, and a swapped-in dataset over other arrays re-uploads."""
        base = getattr(self.data, "base", None)
        features = getattr(base, "features", None)
        labels = getattr(base, "labels", None)
        if not hasattr(self.data, "client_indices") or features is None \
                or labels is None:
            return None
        held = self._samples
        if held is None or held[0] is not features or held[1] is not labels:
            held = self._samples = (features, labels,
                                    jax.device_put(features),
                                    jax.device_put(labels))
            self._eval_batches = {}
        return held[2], held[3]

    def _collect_batches(self, round_idx: int, ids=None):
        """Per-client step inputs, bucketed by batch shape.

        Returns [(client_ids, inputs)]. With the sample set on the device
        (:meth:`_device_samples`) ``inputs`` is the bucket's int32 table
        of positions into it, (C_bucket, local_steps, batch); otherwise a
        dict of stacked batches, (C_bucket, local_steps, batch, ...).
        Either way the values are the ones the loop engine feeds step by
        step. ``ids`` restricts the cohort (the online track trains
        partial cohorts); ``None`` means every client, in id order.
        """
        with tracing.span("round.inputs"):
            if ids is None:
                ids = range(self.hierarchy.total_clients)
            first = round_idx * self.local_steps
            steps = range(first, first + self.local_steps)
            if self._device_samples() is not None:
                tables: Dict[int, list] = {}
                for c in ids:
                    c = int(c)
                    rows = [self.data.client_indices(c, self.batch_size, s)
                            for s in steps]
                    tables.setdefault(len(rows[0]), []).append((c, rows))
                return [(np.asarray([c for c, _ in entries], np.int64),
                         np.array([rows for _, rows in entries], np.int32))
                        for entries in tables.values()]
            buckets: Dict[tuple, list] = {}
            for c in ids:
                c = int(c)
                batches = [self.data.client_batch(c, self.batch_size, s)
                           for s in steps]
                sig = tuple(sorted((k, v.shape, str(np.asarray(v).dtype))
                                   for k, v in batches[0].items()))
                buckets.setdefault(sig, []).append((c, batches))
            out = []
            for entries in buckets.values():
                keys = entries[0][1][0].keys()
                stacked = {k: np.stack([np.stack([np.asarray(b[k])
                                                  for b in batches])
                                        for _, batches in entries])
                           for k in keys}
                out.append((np.asarray([c for c, _ in entries], np.int64),
                            stacked))
            return out

    def _local_fn_for(self, indexed: bool) -> Callable:
        """The jitted ``local_all``; jit compiles it once per bucket
        shape. ``indexed`` takes ``(params, features, labels, idx)`` and
        gathers each step's batch from the sample set inside the scan
        (the set comes in as arguments, never as a constant of the
        program); otherwise ``(params, batches)``, stacked on the host."""
        fn = self._local_fns.get(indexed)
        if fn is not None:
            return fn
        loss_fn = self.model.loss_fn
        lr = self.local_lr
        per_client = self._per_client

        def train(params, steps, batch_of):
            def step(p, s):
                b = batch_of(s)
                if per_client:
                    (lval, m), g = jax.value_and_grad(
                        lambda q: loss_fn(q, b), has_aux=True)(p)
                    out = (lval, m.get("moe_held", jnp.int32(0)))
                else:
                    lval, g = jax.value_and_grad(
                        lambda q: loss_fn(q, b)[0])(p)
                    out = lval
                return jax.tree.map(
                    lambda x, gg: x - lr * gg, p, g), out

            # unrolled, each step's gradient is freed before the next: a
            # loop would keep the weights twice in its state (a model at
            # published widths needs that room)
            final, outs = jax.lax.scan(step, params, steps,
                                       unroll=per_client)
            if per_client:
                return final, (outs[0][-1], jnp.sum(outs[1]))
            return final, outs[-1]

        # clients ride a vmap, unless the model does not batch over them
        over_clients = jax.vmap if not per_client else \
            (lambda f: lambda xs: jax.lax.map(f, xs))
        if indexed:
            def local_all(params, features, labels, idx):
                return over_clients(lambda rows: train(
                    params, rows,
                    lambda i: {"x": features[i], "y": labels[i]}))(idx)
        else:
            def local_all(params, batches):
                return over_clients(lambda client_batches: train(
                    params, client_batches, lambda b: b))(batches)

        fn = jax.jit(local_all, static_argnames=())
        self._local_fns[indexed] = fn
        return fn

    def _dispatch_local(self, round_idx: int, ids=None):
        """One ``local_all`` call per batch-shape bucket of the cohort
        (``ids``; ``None`` = every client). Returns ([(bucket client ids,
        updated params)], wall seconds until the last call is done)."""
        t0 = time.perf_counter()
        pieces: List[Tuple[np.ndarray, object]] = []
        outs = []
        for bucket_ids, inputs in self._collect_batches(round_idx, ids):
            new_p, out = self._local_call(inputs)
            pieces.append((bucket_ids, new_p))
            outs.append(out)
        with tracing.span("round.wait"):
            jax.block_until_ready(jax.tree.leaves(pieces[-1][1])[0])
        self._count_held(outs)
        return pieces, time.perf_counter() - t0

    def _local_call(self, inputs):
        """One ``local_all`` dispatch over a bucket (or a chunk of one):
        ``(updated params stacked over its clients, its outputs)``."""
        indexed = isinstance(inputs, np.ndarray)
        args = (*self._device_samples(), inputs) if indexed else (inputs,)
        new_p, out = self._local_fn_for(indexed)(self.params, *args)
        if indexed:
            tracing.count("round.indexed_calls")
        tracing.count("round.local_calls")
        return new_p, out

    def _count_held(self, outs) -> None:
        """Token-expert pairs the held experts computed, read from
        ``local_all`` outputs that are already done (only while the
        profiler records, so an untraced round transfers nothing)."""
        if self._per_client and tracing.recording():
            tracing.count("moe.held_assignments",
                          int(sum(np.sum(np.asarray(o[1])) for o in outs)))

    def _train_all_batched(self, round_idx: int):
        """All clients' local training. Returns (stacked_updates (C,...),
        train_times (C,))."""
        C = self.hierarchy.total_clients
        pieces, wall = self._dispatch_local(round_idx)

        if len(pieces) == 1 and np.array_equal(
                pieces[0][0], np.arange(C)):
            stacked_updates = pieces[0][1]
        else:
            order = np.concatenate([ids for ids, _ in pieces])
            perm = jnp.asarray(np.argsort(order))
            stacked_updates = jax.tree.map(
                lambda *xs: jnp.concatenate(xs, axis=0)[perm],
                *[p for _, p in pieces])

        if self.timing == "deterministic":
            per_client_dt = float(self.local_steps)
        else:
            # one fused dispatch: attribute wall time evenly (the loop
            # engine measures each client; here C clients share the call)
            per_client_dt = wall / C
        train_times = per_client_dt / self.clients.pspeed
        return stacked_updates, train_times

    def _agg_batched(self, stacked_updates, placement: np.ndarray):
        """Per-level segment-sum aggregation + per-cluster timing charge.

        Deterministic timing charges eq. 6 from the plan's ACTUAL member
        payloads (same formula, same rng stream as the loop engine);
        measured timing splits each level's wall clock across its
        clusters by payload share before the pspeed/comm composition.
        """
        with tracing.span("round.merge"):
            plan = self.hierarchy.round_plan(placement)
            if self.timing == "deterministic":
                # charge eq. 6 analytically; run the whole aggregation as
                # ONE jit call (no per-level host syncs needed)
                new_global = self._agg.aggregate_fused(
                    stacked_updates, self.weights, plan)
                return new_global, self._charge_levels(plan)

            weighted = self._agg.weighted(stacked_updates, self.weights)
            walls = []
            vals = None
            for idx in range(len(plan.levels)):
                t0 = time.perf_counter()
                vals = self._agg.run_level(idx, weighted, vals, plan)
                jax.block_until_ready(jax.tree.leaves(vals)[0])
                walls.append(time.perf_counter() - t0)
            return jax.tree.map(lambda x: x[0], vals), \
                self._charge_levels(plan, walls)

    def _client_chunk(self) -> int:
        """Clients whose local training runs at once. Every client fits
        unless the device reports a memory limit that the global model,
        one accumulator per tree level and three model-sized buffers per
        client (its weights, their gradient and one temporary), within
        three quarters of the limit, cannot hold; then as many as fit,
        at least one."""
        C = self.hierarchy.total_clients
        limit = (jax.devices()[0].memory_stats() or {}).get("bytes_limit")
        if not limit:
            return C
        model = sum(x.nbytes for x in jax.tree.leaves(self.params))
        free = 0.75 * limit - (1 + self.hierarchy.depth) * model
        return int(min(C, max(1, free // (3 * model))))

    def _chunk(self) -> int:
        """``_client_chunk`` of the current tree, asked once (at warm-up)
        and again only when the tree changes, not every round."""
        tree = (self.hierarchy.total_clients, self.hierarchy.depth)
        if self._chunk_for is None or self._chunk_for[0] != tree:
            self._chunk_for = (tree, self._client_chunk())
        return self._chunk_for[1]

    def _fold_fns(self) -> tuple:
        """The chunked round's jitted folds, each writing over the
        accumulator it is given: ``fold_start`` (w * u[i]), ``fold_add``
        (acc + w * u[i]) and ``fold_merge`` (acc + child), the weighting
        as ``SegmentAggregator``'s."""
        if self._folds is None:
            def fold_start(stack, i, w):
                return jax.tree.map(
                    lambda x: x[i] * w.astype(x.dtype), stack)

            def fold_add(acc, stack, i, w):
                return jax.tree.map(
                    lambda a, x: a + x[i] * w.astype(x.dtype), acc, stack)

            def fold_merge(acc, child):
                return jax.tree.map(jnp.add, acc, child)

            self._folds = (
                jax.jit(fold_start, static_argnames=()),
                jax.jit(fold_add, static_argnames=(), donate_argnums=(0,)),
                jax.jit(fold_merge, static_argnames=(), donate_argnums=(0,)))
        return self._folds

    def _visit_order(self, placement: np.ndarray) -> list:
        """The tree walked depth first, as a list of ``("client", c,
        slot)`` (fold client c's update into slot's accumulator) and
        ``("merge", child, slot)`` steps. Every cluster folds its host
        first, then its children in order: ``SegmentAggregator``'s
        ``[host, children...]``."""
        h = self.hierarchy
        trainers = h.trainer_assignment(placement)
        leaf0 = h.level_starts[h.depth - 1]
        steps: list = []

        def visit(slot: int) -> None:
            steps.append(("client", int(placement[slot]), slot))
            kids = h.children_slots(slot)
            for k in kids:
                visit(k)
                steps.append(("merge", k, slot))
            if not kids:
                steps.extend(("client", int(t), slot)
                             for t in trainers[slot - leaf0])
        visit(0)
        return steps

    def _round_chunked(self, r: int, placement: np.ndarray, chunk: int):
        """Train the clients ``chunk`` at a time in the tree's depth-first
        order and fold each one's weighted update into its cluster's
        accumulator; a finished cluster folds into its parent's. One
        accumulator per level is live, plus one chunk's training state,
        whatever the number of clients. Returns the new global params."""
        fold_start, fold_add, fold_merge = self._fold_fns()
        where = {}
        buckets = self._collect_batches(r)
        for b, (ids, _) in enumerate(buckets):
            for row, c in enumerate(ids.tolist()):
                where[c] = (b, row)
        steps = self._visit_order(placement)
        order = [c for kind, c, _ in steps if kind == "client"]
        acc: dict = {}
        held: Optional[dict] = None   # client -> (chunk's updates, row)
        outs = []
        pos = 0
        for kind, c, slot in steps:
            if kind == "merge":
                with tracing.span("round.chunk"):
                    acc[slot] = fold_merge(acc[slot], acc.pop(c))
                continue
            if held is None:
                # the next chunk: consecutive clients of one input bucket
                b = where[order[pos]][0]
                ids = []
                while pos < len(order) and len(ids) < chunk and \
                        where[order[pos]][0] == b:
                    ids.append(order[pos])
                    pos += 1
                with tracing.span("round.chunk"):
                    rows = np.asarray([where[i][1] for i in ids])
                    inputs = buckets[b][1]
                    inputs = inputs[rows] if isinstance(inputs, np.ndarray) \
                        else {k: v[rows] for k, v in inputs.items()}
                    stack, out = self._local_call(inputs)
                    outs.append(out)
                    tracing.count("round.client_chunks")
                held = {i: (stack, j) for j, i in enumerate(ids)}
            stack, j = held[c]
            with tracing.span("round.chunk"):
                w = jnp.float32(self.weights[c])
                acc[slot] = fold_start(stack, j, w) if slot not in acc \
                    else fold_add(acc[slot], stack, j, w)
            if c == ids[-1]:
                held = stack = None     # the chunk's updates are folded
                with tracing.span("round.wait"):
                    jax.block_until_ready(jax.tree.leaves(acc[slot])[0])
        self._count_held(outs)
        return acc[0]

    def _charge_levels(self, plan, walls=None) -> float:
        """eq. 7 over the plan's levels: the sum of each level's slowest
        cluster. Deterministic timing charges eq. 6 from the plan's
        ACTUAL member payloads (same formula, same rng stream as the
        loop engine); measured timing splits each level's wall seconds
        (``walls``) across its clusters by payload share."""
        h = self.hierarchy
        mds = self.clients.mdatasize
        total = 0.0
        for idx, lp in enumerate(plan.levels):
            loads = np.zeros(lp.n_clusters)
            np.add.at(loads, lp.seg, mds[lp.member_clients])
            if walls is None:
                cluster_dt = loads / self.EQ6_PAYLOAD_SCALE
            else:
                cluster_dt = walls[idx] * loads / max(loads.sum(), 1e-12)
            # pspeed/comm/noise composition, vectorized per level (one rng
            # draw per cluster, same stream order as the loop engine)
            ts = (cluster_dt / self.clients.pspeed[lp.hosts]
                  + self.comm_latency * lp.n_parts)
            if self.rng_noise:
                ts = ts * (1.0 + self.rng.normal(0, self.rng_noise,
                                                 size=lp.n_clusters))
            if self._trace is not None:
                level = h.depth - 1 - idx  # plan levels are deepest first
                start = h.level_starts[level]
                self._trace["levels"].append({
                    "level": level,
                    "slots": list(range(start, start + lp.n_clusters)),
                    "hosts": lp.hosts.tolist(),
                    "loads": np.asarray(loads, np.float64).tolist(),
                    "n_parts": lp.n_parts.tolist(),
                    "delays": np.asarray(ts, np.float64).tolist()})
            total += float(ts.max())
        return total

    def _round_batched(self, r: int, placement: np.ndarray):
        if self._agg is None:
            self._agg = SegmentAggregator(self.hierarchy)
        chunk = self._chunk()
        if chunk < self.hierarchy.total_clients:
            if self.timing != "deterministic":
                raise ValueError(
                    "a round trained in client chunks charges eq. 6 "
                    "analytically and needs timing='deterministic'")
            new_params = self._round_chunked(r, placement, chunk)
            train_times = float(self.local_steps) / self.clients.pspeed
            if self._trace is not None:
                self._trace["train"] = {
                    "clients": list(range(self.hierarchy.total_clients)),
                    "times": np.asarray(train_times, np.float64).tolist()}
            with tracing.span("round.merge"):
                agg_time = self._charge_levels(
                    self.hierarchy.round_plan(placement))
            return new_params, float(np.max(train_times)), agg_time
        stacked_updates, train_times = self._train_all_batched(r)
        if self._trace is not None:
            self._trace["train"] = {
                "clients": list(range(self.hierarchy.total_clients)),
                "times": np.asarray(train_times, np.float64).tolist()}
        new_params, agg_time = self._agg_batched(stacked_updates, placement)
        return new_params, float(np.max(train_times)), agg_time

    # ==================================================================
    # partial-cohort hooks (the online track's building blocks)
    # ==================================================================
    def train_cohort(self, ids, round_idx: int):
        """Local training for a client subset, from the CURRENT global.

        ``ids`` must be strictly increasing. Returns ``(stacked_updates,
        train_times)`` row-aligned to ``ids``. A full-population cohort
        routes through ``_train_all_batched`` — the exact executable
        ``run_round`` uses — so a full-cohort call is bit-identical to
        the synchronous round's training half (the degenerate parity
        pin rides on this). Partial cohorts share the same per-bucket
        jit'd fns; only the leading client axis differs.
        """
        ids = np.asarray(ids, np.int64)
        self._check_population()
        C = self.hierarchy.total_clients
        if ids.size and np.any(np.diff(ids) <= 0):
            raise ValueError("train_cohort ids must be strictly increasing")
        if ids.size == C:
            return self._train_all_batched(round_idx)
        if ids.size == 0:
            return None, np.zeros(0, np.float64)
        pieces, wall = self._dispatch_local(round_idx, ids)

        order = np.concatenate([b for b, _ in pieces])
        if len(pieces) == 1 and np.array_equal(order, ids):
            stacked_updates = pieces[0][1]
        else:
            # rows land in bucket order; argsort restores ascending id
            # order == the ids order (ids are strictly increasing)
            perm = jnp.asarray(np.argsort(order))
            stacked_updates = jax.tree.map(
                lambda *xs: jnp.concatenate(xs, axis=0)[perm],
                *[p for _, p in pieces])

        if self.timing == "deterministic":
            per_client_dt = float(self.local_steps)
        else:
            per_client_dt = wall / ids.size
        train_times = per_client_dt / self.clients.pspeed[ids]
        return stacked_updates, train_times

    def aggregate_cohort(self, stacked_updates, placement):
        """Full-population hierarchical aggregation: the batched
        engine's fused segment-sum path, returning ``(new_global,
        agg_time)`` WITHOUT committing the params (callers decide —
        the online track's degenerate rounds commit via
        :meth:`set_global`). Bit-identical to ``run_round``'s
        aggregation half."""
        placement = np.asarray(placement, np.int64)
        self.hierarchy.validate_placement(placement)
        if self._agg is None:
            self._agg = SegmentAggregator(self.hierarchy)
        return self._agg_batched(stacked_updates, placement)

    def cluster_delay(self, host: int, member_clients, n_parts: int
                      ) -> float:
        """The eq. 6 delay one aggregation flush charges: payload work
        over the ACTUAL members' model sizes, scaled by the host's
        pspeed plus per-part comm latency — the same composition the
        synchronous engines charge per cluster, exposed for the online
        track's per-flush timing."""
        dt = self._det_cluster_work(member_clients)
        return self._cluster_time(int(host), dt, int(n_parts))

    def evaluate_global(self) -> tuple:
        """(loss, accuracy) of the current global params — the same
        eval batch/executable ``run_round`` scores with."""
        return self._evaluate()

    def set_global(self, params) -> None:
        """Commit a new global model (the online root merge's result)."""
        self.params = params

    # ==================================================================
    def _evaluate(self, n: int = 512) -> tuple:
        with tracing.span("round.eval"):
            if hasattr(self.data, "eval_batch"):
                batch = self.data.eval_batch(n)
            else:
                batch = self._base_eval_batch(n)
            loss, metrics = self._eval(self.params, batch)
            with tracing.span("round.wait"):
                out = float(loss), float(metrics.get("acc", 0.0))
            if "moe_held" in metrics and tracing.recording():
                tracing.count("moe.eval_held_assignments",
                              int(metrics["moe_held"]))
            return out

    def _base_eval_batch(self, n: int) -> dict:
        """The first ``n`` samples of the base set: sliced once from its
        device copy where there is one, else gathered on the host."""
        samples = self._device_samples()
        if samples is None:
            base = self.data.base
            idx = np.arange(min(n, len(base)))
            return {"x": base.features[idx], "y": base.labels[idx]}
        batch = self._eval_batches.get(n)
        if batch is None:
            features, labels = samples
            batch = self._eval_batches[n] = {"x": features[:n],
                                             "y": labels[:n]}
        return batch

    # ------------------------------------------------------------------
    def warmup(self) -> None:
        """Trace/compile everything once so round-0 timing is not skewed
        by compilation (the docker system has no such artifact)."""
        if self.engine == "batched":
            if self._agg is None:
                self._agg = SegmentAggregator(self.hierarchy)
            placement = np.arange(self.hierarchy.dimensions)
            chunk = self._chunk()
            if chunk < self.hierarchy.total_clients:
                self._round_chunked(0, placement, chunk)
                self._evaluate()
                return
            stacked, _ = self._train_all_batched(0)
            noise, self.rng_noise = self.rng_noise, 0.0  # keep rng stream
            try:
                self._agg_batched(stacked, placement)
            finally:
                self.rng_noise = noise
            self._evaluate()
            return
        batch = self.data.client_batch(0, self.batch_size, 0)
        lval, g = self._grad_step(self.params, batch)
        jax.block_until_ready(lval)
        h = self.hierarchy
        n_pool = h.total_clients - h.dimensions
        base, extra = divmod(n_pool, h.n_leaves)
        sizes = {h.width + 1, base + 1} | ({base + 2} if extra else set())
        for k in sorted(sizes):
            acc = self._wsum([self.params] * k, [1.0] * k)
            jax.block_until_ready(jax.tree.leaves(acc)[0])
        self._evaluate()

    # kept as an alias for callers of the historical private name
    _warmup = warmup

    # ==================================================================
    # elastic population: admit / retire / sync_population
    # ==================================================================
    def admit(self, memcap, pspeed, mdatasize=None
              ) -> Tuple[np.ndarray, Optional[TopologyUpdate]]:
        """Admit fresh clients into the LIVE training population.

        Appends the devices to the pool, provisions each a data shard
        (``FederatedDataset.resize`` — survivors keep their exact
        shards), recomputes the FedAvg weights, and re-hierarchizes when
        the growth crosses the tree's capacity window. Returns ``(new
        client ids, TopologyUpdate or None)`` — callers driving a
        placement strategy must ``strategy.migrate(update)`` before the
        next ``run_round``, exactly as the experiment runner does.

        Joiners hold no model/optimizer state of their own: every round
        starts each client's local steps from the CURRENT global
        ``self.params``, so a mid-run joiner's first gradient step is
        taken from the model the federation has already trained — never
        from the round-0 init (pinned by the elastic-emulated tests).
        """
        ids = self.clients.join(memcap, pspeed, mdatasize)
        return ids, self.sync_population()

    def retire(self, ids) -> Optional[TopologyUpdate]:
        """Retire clients from the live population: their data shards
        are dropped, survivors are renumbered contiguously, and the
        returned :class:`TopologyUpdate` carries the old->new id remap
        plus the ``slot_remap`` every strategy's ``migrate`` hook uses
        to REPAIR placements — a departure taking out a current
        aggregator host yields a valid repaired placement for the very
        next round."""
        self.clients.leave(ids)
        return self.sync_population()

    def sync_population(self) -> Optional[TopologyUpdate]:
        """Reconcile hierarchy + data + engine state with the (possibly
        resized) client pool; ``None`` when the population is untouched.

        This is the emulated twin of
        ``SimulatedEnvironment.sync_topology``: it drains the pool's
        resize log, carries surviving data shards across the id remap
        (provisioning joiners via ``repro.data.synthetic``), recomputes
        the FedAvg weights, re-hierarchizes through the SAME
        capacity-window rule (:func:`elastic_rehierarchize`, so both
        tracks replay identical hierarchy sequences for one event
        schedule), and retargets the batched round engine — the
        segment-sum executables are re-jitted only when the tree shape
        actually changed. Events mutating the pool directly
        (``ClientJoin``/``ClientLeave``) are wired here through
        ``EmulatedEnvironment.sync_topology``.
        """
        drained = self.clients.drain_resizes()
        if drained is None:
            return None
        old_n, client_remap = drained
        old_h = self.hierarchy
        if old_n != old_h.total_clients:
            raise RuntimeError(
                f"pool resize log starts at {old_n} clients but the "
                f"hierarchy tracked {old_h.total_clients}")
        n = len(self.clients)
        resize = getattr(self.data, "resize", None)
        if resize is None:
            raise NotImplementedError(
                f"{type(self.data).__name__} has no resize(); elastic "
                f"populations need a dataset that can carry shards "
                f"across a pool resize")
        resize(client_remap, n, self._elastic_rng)
        self.weights = self.data.client_weights()
        new_h, self._capacity = elastic_rehierarchize(old_h, n,
                                                      self._capacity)
        self.topology_version += 1
        update = TopologyUpdate(
            version=self.topology_version,
            old_hierarchy=old_h, new_hierarchy=new_h,
            slot_remap=slot_remap(old_h, new_h),
            client_remap=client_remap)
        self.hierarchy = new_h
        if self._agg is not None:
            self._agg.retarget(new_h)
        return update

    def _check_population(self) -> None:
        """Round-time invariant: the population must be synced."""
        if self.clients.pending_remap() is not None:
            raise RuntimeError(
                "client pool was resized without sync_population(); use "
                "admit()/retire() (or drive rounds through "
                "EmulatedEnvironment, whose sync_topology wires "
                "ClientJoin/ClientLeave events here)")
        if not (len(self.clients) == self.hierarchy.total_clients
                == self.data.n_clients):
            raise RuntimeError(
                f"inconsistent population: pool={len(self.clients)} "
                f"hierarchy={self.hierarchy.total_clients} "
                f"data={self.data.n_clients}")

    def run_round(self, r: int, placement) -> RoundRecord:
        """Execute ONE federated round at ``placement`` and return its
        record (the black-box TPD plus train/agg split and eval metrics).

        This is the single step both ``run`` and the experiment API's
        ``EmulatedEnvironment`` drive, so a strategy observed through
        either path sees bit-identical TPDs. Call ``warmup()`` once
        before the first round.
        """
        with tracing.span("round", round=r):
            placement = np.asarray(placement, np.int64)
            self._check_population()
            self.hierarchy.validate_placement(placement)

            self.last_timings = None
            if self.record_timings:
                self._trace = {"train": {"clients": [], "times": []},
                               "levels": []}
            try:
                if self.engine == "loop":
                    new_params, train_time, agg_time = \
                        self._round_loop(r, placement)
                else:
                    new_params, train_time, agg_time = \
                        self._round_batched(r, placement)
            finally:
                if self._trace is not None:
                    self._trace["train_time"] = 0.0
                    self._trace["agg_time"] = 0.0
                    self.last_timings, self._trace = self._trace, None
            self.params = new_params
            if self.last_timings is not None:
                self.last_timings["train_time"] = float(train_time)
                self.last_timings["agg_time"] = float(agg_time)

            tpd = (train_time + agg_time) * self.time_scale
            loss, acc = self._evaluate()
            return RoundRecord(
                round_idx=r, placement=placement.tolist(), tpd=tpd,
                train_time=train_time, agg_time=agg_time,
                loss=loss, accuracy=acc)

    def run_round_faulty(self, r: int, placement, *, down=(), dropped=(),
                         degraded=None, quorum_frac: float = 0.0
                         ) -> Tuple[RoundRecord, Dict[str, float]]:
        """One federated round under faults (the emulated track's fault
        path; ``repro.faults``).

        ``down`` clients (crashed or partitioned this round) neither
        train nor deliver; ``dropped`` clients train but their updates
        are lost in transit; ``degraded`` maps clients to train-delay
        multipliers. Down aggregator HOSTS fail over to the lowest-id
        live unplaced client (black-box: no pspeed peeking). Surviving
        updates merge FLAT at the root — hierarchical FedAvg over the
        tree equals flat weighted FedAvg (the segment-sum invariant) —
        through :func:`quorum_merge_batched`, gated on
        live-population quorum and damped by the arrived fraction; a
        refused merge leaves the model untouched (a degraded flush).
        Aggregation time charges the eq. 6 per-cluster walk over the
        payloads actually present.

        A round with NO faults delegates to :meth:`run_round` verbatim,
        so a zero-fault schedule stays bit-identical to the fault-free
        track (the parity pin). Returns ``(record, extra)`` where
        ``extra`` carries the fault series (merged / degraded_flushes /
        failovers / dropped_updates / down).
        """
        placement = np.asarray(placement, np.int64)
        self._check_population()
        self.hierarchy.validate_placement(placement)
        down = {int(c) for c in down}
        dropped = {int(c) for c in dropped}
        degraded = {int(c): float(f)
                    for c, f in sorted((degraded or {}).items())}
        C = self.hierarchy.total_clients
        if not down and not dropped and not degraded:
            rec = self.run_round(r, placement)
            return rec, {"merged": float(C), "degraded_flushes": 0.0,
                         "failovers": 0.0, "dropped_updates": 0.0,
                         "down": 0.0}
        if self.timing != "deterministic":
            raise ValueError(
                "run_round_faulty composes per-cluster delays "
                "analytically and needs timing='deterministic', got "
                f"{self.timing!r}")
        if self.engine != "batched":
            raise ValueError("run_round_faulty needs the batched round "
                             f"engine, got {self.engine!r}")

        cohort = np.asarray([c for c in range(C) if c not in down],
                            np.int64)
        if cohort.size == 0:
            raise RuntimeError(f"round {r}: every client is down")

        # aggregator failover: repair down hosts before anything runs
        eff = placement.copy()
        placed = {int(c) for c in eff}
        failovers = 0
        for s in range(len(eff)):
            if int(eff[s]) in down:
                repl = -1
                for c in range(C):
                    if c not in down and c not in placed:
                        repl = c
                        break
                if repl < 0:
                    raise RuntimeError(
                        f"aggregator failover for slot {s}: no live "
                        "unplaced client left")
                eff[s] = repl
                placed.add(repl)
                failovers += 1
        self.hierarchy.validate_placement(eff)

        stacked, train_times = self.train_cohort(cohort, r)
        train_times = np.asarray(train_times, np.float64).copy()
        for j in range(cohort.size):
            factor = degraded.get(int(cohort[j]))
            if factor is not None:
                train_times[j] *= factor
        train_time = float(train_times.max())

        merged_ids = np.asarray(
            [c for c in cohort.tolist() if c not in dropped], np.int64)
        need = quorum_count(max(1, C - len(down)), quorum_frac)
        if merged_ids.size < need:
            agg_time = 0.0
            merged = 0
            degraded_flush = 1.0
        else:
            rows = np.searchsorted(cohort, merged_ids)
            sub = jax.tree.map(lambda x: x[jnp.asarray(rows)], stacked)
            base_w = self.weights[merged_ids]
            stal = np.zeros(merged_ids.size, np.float64)
            self.params = quorum_merge_batched(
                self.params, sub, base_w, stal, 0.0, 1.0,
                merged_ids.size / C)
            agg_time = self._faulty_agg_time(
                eff, {int(c) for c in merged_ids})
            merged = int(merged_ids.size)
            degraded_flush = 0.0

        tpd = (train_time + agg_time) * self.time_scale
        loss, acc = self._evaluate()
        rec = RoundRecord(
            round_idx=r, placement=eff.tolist(), tpd=tpd,
            train_time=train_time, agg_time=agg_time,
            loss=loss, accuracy=acc)
        extra = {
            "merged": float(merged),
            "degraded_flushes": degraded_flush,
            "failovers": float(failovers),
            "dropped_updates": float(
                len(dropped & {int(c) for c in cohort})),
            "down": float(len(down))}
        return rec, extra

    def _faulty_agg_time(self, placement: np.ndarray, merged: set
                         ) -> float:
        """eq. 7 composition of eq. 6 per-cluster delays over the
        payloads PRESENT under faults: a leaf cluster charges its
        merged trainers (plus the host's own update if it merged), an
        inner cluster charges its child hosts' forwarded partials.
        Reduces to the full ``_aggregate`` walk when everything merged."""
        h = self.hierarchy
        trainers = h.trainer_assignment(placement)
        leaf_start = h.level_starts[h.depth - 1]
        total = 0.0
        for level in range(h.depth - 1, -1, -1):
            level_max = 0.0
            for s in range(h.level_starts[level],
                           h.level_starts[level + 1]):
                host = int(placement[s])
                kids = h.children_slots(s)
                if kids:
                    present = [int(placement[k]) for k in kids]
                else:
                    li = s - leaf_start
                    present = [t for t in trainers[li] if t in merged]
                if host in merged:
                    present = [host] + present
                if not present:
                    continue
                dt = self._det_cluster_work(present)
                level_max = max(
                    level_max,
                    self._cluster_time(host, dt, len(present)))
            total += level_max
        return total

    # ==================================================================
    # checkpoint support: the non-pytree runtime state
    # ==================================================================
    def runtime_state(self) -> dict:
        """JSON-safe snapshot of the orchestrator state that is NOT the
        params pytree (which checkpoints through the npz payload): the
        rng stream positions and the elastic bookkeeping. Restoring
        both makes a resumed run replay bit-identically."""
        return {"rng": self.rng.bit_generator.state,
                "elastic_rng": self._elastic_rng.bit_generator.state,
                "topology_version": int(self.topology_version),
                "capacity": int(self._capacity)}

    def load_runtime_state(self, state: dict) -> None:
        self.rng.bit_generator.state = state["rng"]
        self._elastic_rng.bit_generator.state = state["elastic_rng"]
        self.topology_version = int(state["topology_version"])
        self._capacity = int(state["capacity"])

    def run(self, strategy: PlacementStrategy, rounds: int,
            verbose: bool = False) -> FederatedRunResult:
        result = FederatedRunResult(strategy=strategy.name)
        self.warmup()
        for r in range(rounds):
            placement = np.asarray(strategy.propose(r), np.int64)
            record = self.run_round(r, placement)
            strategy.observe(placement, record.tpd)
            result.rounds.append(record)
            if verbose:
                print(f"[{strategy.name}] round {r:3d} "
                      f"tpd={record.tpd:8.4f} "
                      f"loss={record.loss:.4f} acc={record.accuracy:.3f}")
        return result
