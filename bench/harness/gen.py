"""Inputs made from ``--seed``: client pools, the synthetic classification
set and its non-IID split, and the MLP's initial weights.

Every draw comes from a named stream of ``numpy.random.SeedSequence``
keyed on the run's seed, so one seed gives the same inputs in every
process, and the program under test receives only the arrays. The
classification set and the Dirichlet split follow the algorithm of
``repro.data.synthetic`` (class-conditional Gaussians; per-class
Dirichlet proportions with a floor per client), copied here so that the
yardstick does not move with the program.
"""
from __future__ import annotations

import numpy as np

# named streams, one per kind of input
POOL, DATA, SPLIT, WEIGHTS, PROGRAM, SAMPLE = range(6)


def stream(seed: int, name: int, *more: int) -> np.random.Generator:
    """The generator of one named stream of ``seed`` (any size of int)."""
    return np.random.default_rng(np.random.SeedSequence(
        [int(seed) & 0xFFFFFFFF, int(seed) >> 32, name, *more]))


def seed31(seed: int, name: int = PROGRAM, *more: int) -> int:
    """A 31-bit seed for the program's own generators."""
    ss = np.random.SeedSequence(
        [int(seed) & 0xFFFFFFFF, int(seed) >> 32, name, *more])
    return int(ss.generate_state(1)[0] >> 1)


def pool(spec: dict, n_clients: int, rng: np.random.Generator) -> dict:
    """Client attributes of one pool, float64.

    ``spec["kind"] == "uniform"``: memcap ~ U[memcap), pspeed ~
    U[pspeed), mdatasize constant (the paper's Sec. IV-A draws).
    ``"explicit"``: every attribute listed, one entry per client.
    """
    if spec["kind"] == "uniform":
        memcap = rng.uniform(*spec["memcap"], n_clients)
        pspeed = rng.uniform(*spec["pspeed"], n_clients)
    elif spec["kind"] == "explicit":
        memcap = np.asarray(spec["memcap"], np.float64)
        pspeed = np.asarray(spec["pspeed"], np.float64)
        if len(memcap) != n_clients or len(pspeed) != n_clients:
            raise ValueError(f"explicit pool lists {len(pspeed)} clients, "
                             f"the cell has {n_clients}")
    else:
        raise ValueError(f"unknown pool kind {spec['kind']!r}")
    return {"memcap": np.asarray(memcap, np.float64),
            "pspeed": np.asarray(pspeed, np.float64),
            "mdatasize": np.full(n_clients, float(spec["mdatasize"]))}


def classification(spec: dict, n_features: int, n_classes: int,
                   rng: np.random.Generator) -> tuple:
    """(features (N, F) float32, labels (N,) int32): class-conditional
    Gaussians, centres ~ N(0, 1), noise ~ N(0, spec["noise"])."""
    n = int(spec["n_samples"])
    centers = rng.normal(size=(n_classes, n_features)).astype(np.float32)
    labels = rng.integers(0, n_classes, size=n).astype(np.int32)
    noise = rng.normal(scale=float(spec["noise"]),
                       size=(n, n_features)).astype(np.float32)
    return centers[labels] + noise, labels


def dirichlet_split(labels: np.ndarray, n_clients: int, alpha: float,
                    min_per_client: int, rng: np.random.Generator) -> list:
    """Sample indices per client: each class is cut by Dirichlet(alpha)
    proportions; clients under the floor take samples from the richest."""
    n_classes = int(labels.max()) + 1
    parts: list = [[] for _ in range(n_clients)]
    for c in range(n_classes):
        idx = np.where(labels == c)[0]
        rng.shuffle(idx)
        props = rng.dirichlet([alpha] * n_clients)
        cuts = (np.cumsum(props) * len(idx)).astype(int)[:-1]
        for cid, part in enumerate(np.split(idx, cuts)):
            parts[cid].extend(part.tolist())
    for cid in np.argsort([len(p) for p in parts]):
        while len(parts[cid]) < min_per_client:
            donor = max(range(n_clients), key=lambda i: len(parts[i]))
            parts[cid].append(parts[donor].pop())
    return [np.asarray(sorted(p), np.int64) for p in parts]


def mlp_dims(cfg: dict) -> list:
    return [cfg["in_features"], *cfg["hidden"], cfg["classes"]]


def mlp_weights(cfg: dict, seed: int):
    """The MLP's initial weights as [(w (din, dout), b (dout,))], made on
    the default device in one jitted call: w ~ N(0, 1/din), b ~
    N(0, 0.01**2), float32."""
    import jax
    import jax.numpy as jnp

    dims = mlp_dims(cfg)
    key = jax.random.key(seed31(seed, WEIGHTS))

    @jax.jit
    def make(key):
        keys = jax.random.split(key, 2 * (len(dims) - 1))
        out = []
        for i, (din, dout) in enumerate(zip(dims[:-1], dims[1:])):
            w = jax.random.normal(keys[2 * i], (din, dout), jnp.float32)
            b = jax.random.normal(keys[2 * i + 1], (dout,), jnp.float32)
            out.append((w / np.sqrt(din), b * 0.01))
        return out

    return make(key)
