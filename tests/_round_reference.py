"""Plain reference for one federated round: the oracle the round engine
is tested against.

It reads an orchestrator only for its configuration and state (model,
params, data, hierarchy, client pool, FedAvg weights, step settings,
comm latency, noise) and recomputes the round from first principles,
one client and one cluster at a time:

* local training: ``local_steps`` plain SGD steps per client, from the
  current global params, on ``data.client_batch``;
* aggregation: the sequential tree merge
  (:func:`repro.fl.aggregation.hierarchical_fedavg`);
* timing: each client charged ``local_steps / pspeed``; each cluster
  charged eq. 6 over its ACTUAL members' payloads (host plus trainers
  at a leaf, host plus child hosts above), scaled by its host's pspeed,
  plus ``comm_latency`` per part, times ``1 + noise`` drawn from ``rng``
  one cluster at a time, levels deepest first; eq. 7 sums each level's
  slowest cluster.
"""
from __future__ import annotations

import jax
import numpy as np

from repro.fl.aggregation import hierarchical_fedavg
from repro.fl.orchestrator import FederatedRunResult, RoundRecord


def local_sgd(orch, params, client: int, round_idx: int):
    """``orch.local_steps`` SGD steps of one client from ``params``."""
    grad = jax.value_and_grad(orch.model.loss_fn, has_aux=True)
    for s in range(orch.local_steps):
        batch = orch.data.client_batch(client, orch.batch_size,
                                       round_idx * orch.local_steps + s)
        _, g = grad(params, batch)
        params = jax.tree.map(lambda p, gg: p - orch.local_lr * gg,
                              params, g)
    return params


def agg_time(orch, placement, rng) -> float:
    """eq. 7 over eq. 6 cluster delays, walked slot by slot."""
    h = orch.hierarchy
    mds = orch.clients.mdatasize
    pspeed = orch.clients.pspeed
    trainers = h.trainer_assignment(placement)
    total = 0.0
    for level in range(h.depth - 1, -1, -1):
        level_max = 0.0
        for s in range(h.level_starts[level], h.level_starts[level + 1]):
            host = int(placement[s])
            kids = h.children_slots(s)
            if kids:
                members = [host] + [int(placement[k]) for k in kids]
            else:
                members = [host] + [int(t) for t in
                                    trainers[s - h.level_starts[h.depth - 1]]]
            work = float(sum(mds[c] for c in members)) \
                / orch.EQ6_PAYLOAD_SCALE
            t = work / pspeed[host] + orch.comm_latency * len(members)
            if orch.rng_noise:
                t *= 1.0 + rng.normal(0, orch.rng_noise)
            level_max = max(level_max, t)
        total += level_max
    return total


def evaluate(orch, params, n: int = 512) -> tuple:
    """(loss, accuracy) on the dataset's evaluation batch, else the base
    set's first ``n`` samples."""
    if hasattr(orch.data, "eval_batch"):
        batch = orch.data.eval_batch(n)
    else:
        base = orch.data.base
        batch = {"x": base.features[:n], "y": base.labels[:n]}
    loss, metrics = jax.jit(orch.model.loss_fn)(params, batch)
    return float(loss), float(metrics.get("acc", 0.0))


def reference_round(orch, r: int, placement, rng) -> RoundRecord:
    """One round from ``orch.params`` at ``placement``; commits the new
    global params to ``orch`` and returns the round's record."""
    placement = np.asarray(placement, np.int64)
    orch.hierarchy.validate_placement(placement)
    C = orch.hierarchy.total_clients
    updates = [local_sgd(orch, orch.params, c, r) for c in range(C)]
    train_time = float(max(orch.local_steps / orch.clients.pspeed[c]
                           for c in range(C)))
    orch.params = hierarchical_fedavg(updates, list(orch.weights),
                                      orch.hierarchy, placement)
    agg = agg_time(orch, placement, rng)
    loss, acc = evaluate(orch, orch.params)
    return RoundRecord(round_idx=r, placement=placement.tolist(),
                       tpd=(train_time + agg) * orch.time_scale,
                       train_time=train_time, agg_time=agg,
                       loss=loss, accuracy=acc)


def reference_run(orch, strategy, rounds: int, seed: int
                  ) -> FederatedRunResult:
    """``FederatedOrchestrator.run`` recomputed by the reference, its
    noise drawn from a fresh ``default_rng(seed)`` (the orchestrator's
    own stream, which its warm-up leaves untouched)."""
    rng = np.random.default_rng(seed)
    result = FederatedRunResult(strategy=strategy.name)
    for r in range(rounds):
        placement = np.asarray(strategy.propose(r), np.int64)
        record = reference_round(orch, r, placement, rng)
        strategy.observe(placement, record.tpd)
        result.rounds.append(record)
    return result
