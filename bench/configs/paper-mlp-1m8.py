"""Plain reference of ``paper-mlp-1m8`` trained in emulated federated
rounds (paper Sec. IV-C): the MLP classifier, local SGD on every client,
FedAvg over the aggregation tree, and the round's delay.

* Model: ``784 -> 768 -> 768 -> 768 -> 10``, ReLU between layers,
  softmax cross-entropy averaged over the batch.
* Round: every client starts from the global weights and takes
  ``local_steps`` SGD steps of rate ``local_lr``. At local step ``t`` of
  round ``r`` client ``c`` trains on the samples of its shard at
  positions ``default_rng((c, r * local_steps + t)).choice(len(shard),
  min(batch, len(shard)), replace=False)``.
* Merge: the tree's level-by-level weighted sums equal one weighted sum
  over all clients, with weights ``len(shard_c) / sum_k len(shard_k)``.
* Evaluation: the loss of the global weights on the first
  ``eval_samples`` samples of the set.
* Delay (deterministic emulation of eq. 6/7): every client's training
  costs ``local_steps / pspeed_c``, the round waits for the slowest; a
  cluster costs ``sum(mdatasize of its members) / payload_scale /
  pspeed_host + comm_latency * members``; levels add their slowest
  cluster; the sum is scaled by ``time_scale``.

Arithmetic is in ``dtype`` (float32 at the ``highest`` matmul precision
is the reference; bfloat16 is the control).
"""
from __future__ import annotations

import numpy as np

from harness import tree


def batch_positions(shard_len: int, batch: int, client: int, step: int
                    ) -> np.ndarray:
    rng = np.random.default_rng((client, step))
    return rng.choice(shard_len, size=min(batch, shard_len), replace=False)


def _loss(params, x, y):
    import jax
    import jax.numpy as jnp
    h = x
    for i, (w, b) in enumerate(params):
        h = h @ w + b
        if i < len(params) - 1:
            h = jax.nn.relu(h)
    logits = h.astype(jnp.float32)
    gold = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - gold)


def train_rounds(cfg: dict, traffic: dict, init: list, features: np.ndarray,
                 labels: np.ndarray, shards: list, rounds: int,
                 dtype="float32", clients=None, first_round: int = 0) -> dict:
    """Run ``rounds`` federated rounds, the first of them round
    ``first_round``, from ``init`` ([(w, b)] float32).

    Returns ``{"loss": [per round], "params": [after each round]}``, the
    weights as float32 numpy arrays. ``clients`` merges only the first
    that many clients, renormalizing their weights (a fault to read)."""
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(dtype)
    steps, batch = traffic["local_steps"], traffic["batch_size"]
    lr = cfg["local_lr"]
    sizes = np.asarray([len(s) for s in shards], np.float64)
    if clients is not None:
        sizes[clients:] = 0.0
    weights = sizes / sizes.sum()
    by_size: dict = {}
    for c, s in enumerate(shards):
        if sizes[c] > 0:
            by_size.setdefault(min(batch, len(s)), []).append(c)

    def local(p, xs, ys):
        def step(q, xy):
            g = jax.grad(_loss)(q, *xy)
            return jax.tree.map(lambda a, b: a - (lr * b).astype(dt), q, g), None
        return jax.lax.scan(step, p, (xs, ys))[0]

    @jax.jit
    def merged_update(p, xs, ys, w):
        # (K clients) local training, then their weighted sum
        outs = jax.vmap(local, in_axes=(None, 0, 0))(p, xs, ys)
        return jax.tree.map(
            lambda o: jnp.tensordot(w.astype(dt), o, axes=1), outs)

    loss_fn = jax.jit(_loss)
    n_eval = min(cfg["eval_samples"], len(labels))
    x_eval = jnp.asarray(features[:n_eval], dt)
    y_eval = jnp.asarray(labels[:n_eval])
    params = [(jnp.asarray(w, dt), jnp.asarray(b, dt)) for w, b in init]
    out = {"loss": [], "params": []}
    for r in range(first_round, first_round + rounds):
        total = None
        for size, clients in sorted(by_size.items()):
            pos = [[shards[c][batch_positions(len(shards[c]), batch, c,
                                              r * steps + t)]
                    for t in range(steps)] for c in clients]
            idx = np.asarray(pos)                         # (K, steps, size)
            part = merged_update(params, jnp.asarray(features[idx], dt),
                                 jnp.asarray(labels[idx]),
                                 jnp.asarray(weights[clients]))
            total = part if total is None else jax.tree.map(
                jnp.add, total, part)
        params = total
        out["loss"].append(float(loss_fn(params, x_eval, y_eval)))
        out["params"].append([(np.asarray(w, np.float32),
                               np.asarray(b, np.float32)) for w, b in params])
    return out


def round_tpd(placements: np.ndarray, pool: dict, traffic: dict, cfg: dict,
              dtype=np.float64) -> np.ndarray:
    """(R, D) placements -> (R,) round delays (deterministic emulation)."""
    placements = np.asarray(placements, np.int64)
    depth, width = traffic["depth"], traffic["width"]
    pspeed = np.asarray(pool["pspeed"]).astype(dtype)
    train = np.max(dtype(traffic["local_steps"]) / pspeed)
    load, members = tree.slot_loads(placements, pool["mdatasize"], depth,
                                    width, dtype)
    delay = load / dtype(cfg["payload_scale"]) / pspeed[placements] + \
        dtype(traffic["comm_latency"]) * members.astype(dtype)
    agg = tree.sum_of_level_max(delay, depth, width)
    return (train + agg) * dtype(cfg["time_scale"])
