"""Pallas kernel: batched TPD evaluation (paper eqs. 6-7) over a
placement swarm, tiled for the TPU.

The swarm evaluator's hot inner shape is ``(P, D)`` placements against a
``(3, C)`` client-attribute table. The hierarchy is a complete
``width``-ary tree in heap order (``Hierarchy.level_starts``): the kids
of slot ``s`` are slots ``width * s + 1 .. width * s + width``, so level
``l`` is the contiguous slot range ``[a_l, a_l + width**l)`` and the
``w``-th kid of every slot of level ``l`` is the stride-``width`` row
range starting at ``a_{l+1} + w``. The kernel therefore needs no dynamic
gather at all:

* the jitted wrapper gathers every slot host's attributes once
  (``attrs[:, placements]``, one XLA gather) and lays them out
  slot-major, ``(A, D, P)``: slots on sublanes, particles on lanes;
* each grid step takes one 128-particle lane tile, sums every slot's
  kid payloads with ``width`` strided sublane loads, adds the trainer
  loads on the leaf level, applies eq. 6 (and the optional memcap
  penalty), max-reduces each level over its sublane range and sums the
  level maxima deepest level first;
* the output is lane-dense, ``(1, P)``.

The trainer-split leaf loads (each unplaced client, ranked in id order,
trains under leaf ``rank mod L``) are built on the device by
``leaf_loads`` and stream in as a slot-major ``(L, P)`` operand.
``tpd_scores`` jits the two together, so a swarm is scored by one
program that takes the ``(P, D)`` placements and the resident attribute
table and returns the ``(P,)`` TPDs.

Math accumulates in f32 in the same order as the jnp oracle
``kernels.ref.tpd_ref`` (kid payloads summed in kid order, per-level
maxima summed deepest first), so the parity tests pin the two exactly,
and both against the float64 scalar model within f32 tolerance.
``CostModel.batch_tpd`` compiles this kernel for large batches on TPU;
``interpret=True`` runs the same body under the Pallas interpreter on
any host, which is how the CPU test suite exercises it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128  # particles per grid step: one lane-dense tile
_NEG = -3.4e38  # f32-safe -inf stand-in: floor of every level max


def _tpd_kernel(depth, width, penalty, host_ref, leaf_ref, o_ref):
    # host_ref (A, D, LANES): rows of [mdatasize, pspeed(, memcap)] per
    # slot host; leaf_ref (L, LANES) trainer loads; o_ref (1, LANES)
    starts = _level_starts(depth, width)
    total = jnp.zeros((1, LANES), jnp.float32)
    for lv in range(depth - 1, -1, -1):                  # deepest first
        a, n = starts[lv], width ** lv
        if lv == depth - 1:
            child = leaf_ref[...]
        else:   # kid w of every slot: stride-width rows of level lv + 1
            b = starts[lv + 1]
            child = host_ref[0, pl.ds(b, n, stride=width), :]
            for w in range(1, width):
                child = child + host_ref[0, pl.ds(b + w, n, stride=width), :]
        load = host_ref[0, a:a + n, :] + child
        delay = load / host_ref[1, a:a + n, :]
        if penalty > 0:
            cap = host_ref[2, a:a + n, :]
            over = jnp.maximum(0.0, load - cap)
            delay = delay * (1.0 + penalty * over / jnp.maximum(cap, 1e-9))
        # the floor is an identity on any delay, but keeps a one-slot
        # level's max an explicit op: XLA's CPU backend would otherwise
        # fuse the root's eq. 6 product into the running sum as one
        # multiply-add and the interpreter would drift from the oracle
        total = total + jnp.maximum(jnp.max(delay, axis=0, keepdims=True),
                                    _NEG)
    o_ref[...] = total


def _level_starts(depth: int, width: int) -> list:
    """First slot of each level (and one past the last), heap order —
    ``Hierarchy.level_starts`` for the same shape."""
    starts = [0]
    for lv in range(depth):
        starts.append(starts[-1] + width ** lv)
    return starts


@functools.partial(jax.jit,
                   static_argnames=("depth", "width", "penalty", "interpret"))
def batch_tpd_pallas(placements, attrs, leaf_load, *, depth: int,
                     width: int, penalty: float = 0.0,
                     interpret: bool = False) -> jnp.ndarray:
    """placements (P, D) int32, attrs (3, C) f32 = [mdatasize, pspeed,
    memcap], leaf_load (P, L) f32 -> (P,) f32 TPDs for the complete
    ``width``-ary tree of ``depth`` levels.

    The grid walks 128-particle lane tiles; rows past ``P`` are padded
    with copies of row 0 and sliced off.
    """
    P, D = placements.shape
    L = leaf_load.shape[1]
    if D != _level_starts(depth, width)[-1] or L != width ** (depth - 1):
        raise ValueError(f"placements {placements.shape} / leaf_load "
                         f"{leaf_load.shape} do not fit a depth-{depth} "
                         f"width-{width} tree")
    pad = (-P) % LANES
    if pad:  # pad with copies of row 0 (any valid row; sliced off below)
        placements = jnp.concatenate(
            [placements, jnp.broadcast_to(placements[:1], (pad, D))])
        leaf_load = jnp.concatenate(
            [leaf_load, jnp.broadcast_to(leaf_load[:1], (pad, L))])
    rows = 3 if penalty > 0 else 2      # memcap only feeds the penalty
    host = jnp.stack([attrs[r].astype(jnp.float32)[placements.T]
                      for r in range(rows)])                  # (A, D, Pp)
    leaf = leaf_load.astype(jnp.float32).T                    # (L, Pp)
    out = pl.pallas_call(
        functools.partial(_tpd_kernel, depth, width, float(penalty)),
        out_shape=jax.ShapeDtypeStruct((1, P + pad), jnp.float32),
        grid=((P + pad) // LANES,),
        in_specs=[pl.BlockSpec((rows, D, LANES), lambda i: (0, 0, i)),
                  pl.BlockSpec((L, LANES), lambda i: (0, i))],
        out_specs=pl.BlockSpec((1, LANES), lambda i: (0, i)),
        interpret=interpret,
    )(host, leaf)
    return out[0, :P]


def leaf_loads(placements, mds, n_leaves: int) -> jnp.ndarray:
    """placements (P, D) int32, mds (C,) f32 -> (P, L) f32 trainer loads.

    Every client a row does not place is a trainer; ranked in ascending
    id order, rank ``r`` trains under leaf ``r mod L``, and a leaf's load
    is the sum of its trainers' ``mds``. A row may repeat an id (legal
    for the model): it then leaves more trainers.

    Dense, with no scatter or gather over ``P * C``. The placed mask is
    a ``P * D`` scatter. Trainer ``c`` has rank ``c - s``, where ``s``
    is the number of placed ids below it, so its payload moves ``s``
    columns to the left: one binary digit of ``s`` a step, the least
    significant first. Along a row ``s`` never falls and ``c - s`` rises
    from trainer to trainer, so no two payloads ever meet. The row,
    padded to a multiple of ``L`` and folded to ``(P, C / L, L)``, then
    sums rank ``r`` into column ``r mod L``.
    """
    P, D = placements.shape
    C = mds.shape[0]
    placed = jnp.zeros((P, C), jnp.int32).at[
        jnp.arange(P)[:, None], placements].set(1)
    trainer = placed == 0
    shift = jnp.where(trainer, jnp.cumsum(placed, axis=1) - placed, 0)
    payload = jnp.where(trainer, mds.astype(jnp.float32)[None], 0.0)
    for bit in range(D.bit_length()):      # s <= D distinct placed ids
        moves = ((shift >> bit) & 1) == 1
        payload = _move_left(payload, moves, 1 << bit)
        shift = _move_left(shift, moves, 1 << bit)
    q = -(-C // n_leaves)
    payload = jnp.pad(payload, ((0, 0), (0, q * n_leaves - C)))
    return payload.reshape(P, q, n_leaves).sum(axis=1)


def _move_left(x, moves, k: int):
    """``x`` (P, C) with its ``moves`` entries taken ``k`` columns to the
    left; the columns they leave read 0."""
    moved = jnp.pad(jnp.where(moves, x, 0)[:, k:], ((0, 0), (0, k)))
    return jnp.where(moves, 0, x) + moved


@functools.partial(jax.jit,
                   static_argnames=("depth", "width", "penalty", "interpret"))
def tpd_scores(placements, attrs, *, depth: int, width: int,
               penalty: float = 0.0, interpret: bool = False
               ) -> jnp.ndarray:
    """placements (P, D) int32, attrs (3, C) f32 -> (P,) f32 TPDs: the
    device-built leaf loads (``leaf_loads``) and the kernel
    (``batch_tpd_pallas``) in one program."""
    leaf = leaf_loads(placements, attrs[0], width ** (depth - 1))
    return batch_tpd_pallas(placements, attrs, leaf, depth=depth,
                            width=width, penalty=penalty,
                            interpret=interpret)
