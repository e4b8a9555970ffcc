"""The aggregation tree of the paper (Sec. III), written out plainly.

A complete ``width``-ary tree of ``depth`` levels in heap order: slot 0
is the root, the kids of slot ``s`` are ``width*s + 1 .. width*s +
width``, and level ``l`` holds slots ``[a_l, a_l + width**l)``. A
placement puts one client on each slot. Every client that holds no slot
trains under a leaf: the unplaced clients, in ascending id order, are
dealt round-robin over the leaves (the i-th goes to leaf ``i mod L``).
"""
from __future__ import annotations

import numpy as np


def level_starts(depth: int, width: int) -> list:
    starts = [0]
    for lv in range(depth):
        starts.append(starts[-1] + width ** lv)
    return starts


def leaf_trainer_sums(placements: np.ndarray, values: np.ndarray,
                      depth: int, width: int, dtype) -> np.ndarray:
    """(R, L): per row, the sum of ``values`` over each leaf's trainers.

    ``values`` is (C,) or (R, C). Sums in ``dtype``."""
    R, _ = placements.shape
    C = values.shape[-1]
    L = width ** (depth - 1)
    placed = np.zeros((R, C), bool)
    np.put_along_axis(placed, placements.astype(np.int64), True, axis=1)
    unplaced = ~placed
    vals = np.broadcast_to(values, (R, C)).astype(dtype)
    n_u = unplaced.sum(axis=1)
    if np.all(n_u == n_u[0]):
        # the trainers of leaf j are ranks j, j + L, j + 2L, ...: pad the
        # rank-ordered values to whole rows of L and sum down the columns
        u = vals[unplaced].reshape(R, int(n_u[0]))
        k = -(-u.shape[1] // L)
        u = np.concatenate([u, np.zeros((R, k * L - u.shape[1]), dtype)],
                           axis=1)
        return u.reshape(R, k, L).sum(axis=1, dtype=dtype)
    rank = np.cumsum(unplaced, axis=1) - 1
    leaf = np.where(unplaced, rank % L, L)           # L = not a trainer
    out = np.zeros((R, L + 1), dtype)
    np.add.at(out, (np.arange(R)[:, None], leaf), vals)
    return out[:, :L]


def leaf_trainer_counts(placements: np.ndarray, n_clients: int,
                        depth: int, width: int) -> np.ndarray:
    """(R, L) number of trainers under each leaf."""
    return leaf_trainer_sums(placements, np.ones(n_clients), depth, width,
                             np.int64)


def slot_loads(placements: np.ndarray, payload: np.ndarray, depth: int,
               width: int, dtype) -> tuple:
    """(load (R, D), members (R, D)): eq. 6's numerator per slot — the
    host's own payload plus its kids' hosts' (inner slots) or its
    trainers' (leaves) — and the number of members of that cluster."""
    R, D = placements.shape
    C = payload.shape[-1]
    starts = level_starts(depth, width)
    rows = np.arange(R)[:, None]
    pay = np.broadcast_to(payload, (R, C)).astype(dtype)
    host = pay[rows, placements]                                # (R, D)
    load = host.copy()
    members = np.ones((R, D), np.int64)
    leaf0 = starts[depth - 1]
    for s in range(leaf0):
        kids = list(range(width * s + 1, width * s + width + 1))
        load[:, s] = load[:, s] + host[:, kids].sum(axis=1, dtype=dtype)
        members[:, s] += width
    load[:, leaf0:] = load[:, leaf0:] + leaf_trainer_sums(
        placements, pay, depth, width, dtype)
    members[:, leaf0:] += leaf_trainer_counts(placements, C, depth, width)
    return load, members


def sum_of_level_max(delay: np.ndarray, depth: int, width: int
                     ) -> np.ndarray:
    """Eq. 7: per row, the sum over levels of the level's largest delay."""
    starts = level_starts(depth, width)
    out = np.zeros(delay.shape[0], delay.dtype)
    for lv in range(depth - 1, -1, -1):
        out = out + delay[:, starts[lv]:starts[lv + 1]].max(axis=1)
    return out


def valid_rows(placements: np.ndarray, n_clients: int) -> np.ndarray:
    """(R,) bool: every id in range and no id twice in the row."""
    p = np.sort(placements, axis=1)
    return (p[:, 0] >= 0) & (p[:, -1] < n_clients) & \
        ~np.any(p[:, 1:] == p[:, :-1], axis=1)
