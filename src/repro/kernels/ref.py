"""Plain oracles for every Pallas kernel in this package (jnp, and numpy
for the host-side ``leaf_loads_ref``).

These are the correctness references the kernel tests sweep against
(``assert_allclose`` over shapes x dtypes), and the default compute path
on CPU / in the dry-run (Pallas-TPU kernels do not lower on the CPU
backend; ``interpret=True`` executes them for validation only).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


def fedavg_ref(stacked: jnp.ndarray, weights: jnp.ndarray) -> jnp.ndarray:
    """Weighted sum over the leading (client) dim.

    stacked (K, N); weights (K,) -> (N,). Accumulates in f32.
    """
    w = weights.astype(jnp.float32)
    return jnp.einsum("kn,k->n", stacked.astype(jnp.float32), w).astype(
        stacked.dtype)


def flash_attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        *, causal: bool = True,
                        window: Optional[int] = None,
                        scale: Optional[float] = None) -> jnp.ndarray:
    """Dense-softmax oracle. q (B,Hq,S,hd); k,v (B,Hkv,S,hd) -> like q."""
    b, hq, s, hd = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    kk = jnp.repeat(k, g, axis=1)
    vv = jnp.repeat(v, g, axis=1)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        kk.astype(jnp.float32)) * scale
    i = jnp.arange(s)
    mask = jnp.ones((s, s), bool)
    if causal:
        mask = mask & (i[None, :] <= i[:, None])
    if window is not None:
        mask = mask & (i[None, :] > i[:, None] - window)
    scores = jnp.where(mask[None, None], scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, vv.astype(jnp.float32))
    return out.astype(q.dtype)


def rglru_scan_ref(a: jnp.ndarray, u: jnp.ndarray,
                   h0: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Gated linear recurrence h_t = a_t * h_{t-1} + u_t.

    a, u (B, T, D) -> h (B, T, D). f32 math.
    """
    a32, u32 = a.astype(jnp.float32), u.astype(jnp.float32)
    if h0 is not None:
        u32 = u32.at[:, 0].add(a32[:, 0] * h0.astype(jnp.float32))

    def step(h, au):
        at, ut = au
        h = at * h + ut
        return h, h

    init = jnp.zeros_like(a32[:, 0])
    _, hs = jax.lax.scan(step, init, (a32.swapaxes(0, 1), u32.swapaxes(0, 1)))
    return hs.swapaxes(0, 1).astype(a.dtype)


def tpd_ref(placements, attrs, leaf_load, *, depth: int, width: int,
            penalty: float = 0.0) -> jnp.ndarray:
    """Dense-jnp oracle for the batched TPD kernel (same operands).

    placements (P, D) int32 over the complete ``width``-ary tree of
    ``depth`` levels in heap order; attrs (3, C) = [mdatasize, pspeed,
    memcap]; leaf_load (P, L) trainer loads per leaf aggregator ->
    (P,) TPDs in f32. Kid payloads are gathered through the explicit
    kid-slot table and summed in kid order.
    """
    mds, pspeed, memcap = (a.astype(jnp.float32) for a in attrs)
    D = placements.shape[1]
    leaf_start = D - width ** (depth - 1)
    host_mds = mds[placements]                       # (P, D)
    kid_slots = 1 + width * jnp.arange(leaf_start)[:, None] \
        + jnp.arange(width)[None]                    # (D - L, W)
    kid_mds = host_mds[:, kid_slots]                 # (P, D - L, W)
    child = kid_mds[..., 0]
    for w in range(1, width):
        child = child + kid_mds[..., w]
    child = jnp.concatenate([child, leaf_load.astype(jnp.float32)], axis=1)
    load = host_mds + child
    delay = load / pspeed[placements]
    if penalty > 0:
        cap = memcap[placements]
        over = jnp.maximum(0.0, load - cap)
        delay = delay * (1.0 + penalty * over / jnp.maximum(cap, 1e-9))
    total = jnp.zeros(placements.shape[:1], jnp.float32)
    start = D
    for lv in range(depth - 1, -1, -1):              # deepest first
        n = width ** lv
        total = total + jnp.max(delay[:, start - n:start], axis=1)
        start -= n
    return total


def leaf_loads_ref(placements, mds, n_leaves: int) -> np.ndarray:
    """Host oracle of ``kernels.tpd.leaf_loads``: placements (P, D) int,
    mds (C,) -> (P, L) float32 trainer loads, by two ``np.bincount``s.

    The placed mask counts each row's ids; the unplaced ids' running
    count gives each its rank, and rank ``r`` trains under leaf
    ``r mod L`` (float64 accumulation, rounded to float32 once).
    """
    placements = np.asarray(placements)
    P, C = placements.shape[0], mds.shape[0]
    p_off = np.arange(P)[:, None]
    unplaced = np.bincount((placements + C * p_off).ravel(),
                           minlength=P * C).reshape(P, C) == 0
    t_mds = np.where(unplaced, np.asarray(mds, np.float32)[None],
                     np.float32(0.0))
    leaf_of = (np.cumsum(unplaced, axis=1) - 1) % n_leaves
    return np.bincount((leaf_of + n_leaves * p_off).ravel(),
                       weights=t_mds.ravel(),
                       minlength=P * n_leaves).reshape(
                           P, n_leaves).astype(np.float32)


def fused_adamw_ref(p, g, m, v, lr, bc1, bc2, *, b1=0.9, b2=0.95,
                    eps=1e-8, wd=0.1):
    """Oracle for the fused AdamW kernel. Returns (new_p, new_m, new_v)."""
    p32 = p.astype(jnp.float32)
    g32 = g.astype(jnp.float32)
    m = b1 * m + (1 - b1) * g32
    v = b2 * v + (1 - b2) * jnp.square(g32)
    mhat = m / bc1
    vhat = v / bc2
    delta = mhat / (jnp.sqrt(vhat) + eps) + wd * p32
    return (p32 - lr * delta).astype(p.dtype), m, v
