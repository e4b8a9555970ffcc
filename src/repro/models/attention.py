"""Attention: exact-FLOP blockwise causal attention, sliding-window
attention, and single-token decode against a (ring) KV cache.

Design notes (TPU adaptation):

* The trainer/prefill path is a **binary causal decomposition**: causal
  attention over S splits into two half-length causal problems plus one
  *dense, unmasked* rectangle (second-half queries over first-half keys),
  merged with online softmax. Unlike the usual "mask the upper triangle"
  jnp fallback this does **not** compute-and-discard half the FLOPs, so
  ``cost_analysis`` FLOPs match the true S^2/2 causal cost — the roofline
  numbers stay honest. The Pallas kernel (kernels/flash_attention.py)
  is the on-TPU implementation of the same schedule; this module is its
  oracle and the default CPU/dry-run path.
* Sliding-window attention gathers, per query block, only the
  ``window + block`` keys it can see (dynamic_slice + vmap), so windowed
  FLOPs are O(S * window) — this is what makes ``long_500k`` lowerable
  for attention architectures.
* GQA is handled by folding query heads into groups over the kv heads.

Shapes: q (B, S, Hq, hd); k (B, T, Hkv, hd); v (B, T, Hkv, hd_v), where
the causal path lets hd_v differ from hd (latent attention). All softmax
math in f32.
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


class _Partial(NamedTuple):
    out: jnp.ndarray   # (B, S, Hq, hd) f32, un-normalized (sum of p*v)
    m: jnp.ndarray     # (B, S, Hq) running max
    denom: jnp.ndarray  # (B, S, Hq) running softmax denominator


def _merge(a: _Partial, b: _Partial) -> _Partial:
    m = jnp.maximum(a.m, b.m)
    ea = jnp.exp(a.m - m)
    eb = jnp.exp(b.m - m)
    out = a.out * ea[..., None] + b.out * eb[..., None]
    return _Partial(out=out, m=m, denom=a.denom * ea + b.denom * eb)


def _finalize(p: _Partial, dtype) -> jnp.ndarray:
    return (p.out / jnp.maximum(p.denom, 1e-30)[..., None]).astype(dtype)


def _group_q(q: jnp.ndarray, n_kv: int) -> jnp.ndarray:
    """(B,S,Hq,hd) -> (B,S,Hkv,G,hd)."""
    b, s, hq, hd = q.shape
    return q.reshape(b, s, n_kv, hq // n_kv, hd)


def _attend_dense_core(q, k, v, mask: Optional[jnp.ndarray], scale: float
                       ) -> _Partial:
    """Unmasked-or-masked dense attention partial over one (Sq, Sk) tile.

    q: (B,Sq,Hkv,G,hd); k/v: (B,Sk,Hkv,hd); mask: (Sq,Sk) bool or None.
    """
    b, sq, hkv, g, hd = q.shape
    scores = jnp.einsum("bqhgd,bkhd->bqhgk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if mask is not None:
        scores = jnp.where(mask[None, :, None, None, :], scores, NEG_INF)
    m = jnp.max(scores, axis=-1)
    # guard fully-masked rows (can happen on padded window edges)
    m_safe = jnp.maximum(m, NEG_INF / 2)
    p = jnp.exp(scores - m_safe[..., None])
    if mask is not None:
        p = jnp.where(mask[None, :, None, None, :], p, 0.0)
    denom = jnp.sum(p, axis=-1)
    out = jnp.einsum("bqhgk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return _Partial(out=out.reshape(b, sq, hkv * g, v.shape[-1]),
                    m=m.reshape(b, sq, hkv * g),
                    denom=denom.reshape(b, sq, hkv * g))


# largest (Sq) a single dense tile may materialize; larger rectangles are
# scanned in q-chunks so the scores temp stays O(B * CHUNK * H * Sk) — this
# is what keeps the 32k prefill / 4k train peak memory sane on a 16 GiB chip
_Q_CHUNK = 512


def _attend_dense(q, k, v, mask: Optional[jnp.ndarray], scale: float
                  ) -> _Partial:
    """Dense tile, q-chunked with ``lax.map`` when Sq is large.

    Chunking changes neither FLOPs nor results — only the peak size of the
    scores temporary (and keeps the HLO compact: one mapped body per
    rectangle size instead of unrolled blocks).
    """
    b, sq, hkv, g, hd = q.shape
    if sq <= _Q_CHUNK or sq % _Q_CHUNK != 0 or mask is not None:
        return _attend_dense_core(q, k, v, mask, scale)
    n = sq // _Q_CHUNK
    qc = q.reshape(b, n, _Q_CHUNK, hkv, g, hd).swapaxes(0, 1)

    def one(qi):
        return _attend_dense_core(qi, k, v, None, scale)

    part = jax.lax.map(one, qc)  # leaves: (n, B, CHUNK, ...)

    def unchunk(x):
        x = jnp.moveaxis(x, 0, 1)  # (B, n, CHUNK, ...)
        return x.reshape((b, sq) + x.shape[3:])

    return _Partial(out=unchunk(part.out), m=unchunk(part.m),
                    denom=unchunk(part.denom))


def _causal_partial(q, k, v, scale: float, leaf: int) -> _Partial:
    """Recursive binary decomposition: exact-FLOP causal attention.

    q/k/v aligned: position i of q attends positions <= i of k/v.
    """
    s = q.shape[1]
    if s <= leaf or s % 2 != 0:
        mask = jnp.tril(jnp.ones((s, s), bool))
        return _attend_dense_core(q, k, v, mask, scale)
    half = s // 2
    lo = _causal_partial(q[:, :half], k[:, :half], v[:, :half], scale, leaf)
    hi_diag = _causal_partial(q[:, half:], k[:, half:], v[:, half:], scale, leaf)
    hi_rect = _attend_dense(q[:, half:], k[:, :half], v[:, :half], None, scale)
    hi = _merge(hi_diag, hi_rect)
    return _Partial(out=jnp.concatenate([lo.out, hi.out], axis=1),
                    m=jnp.concatenate([lo.m, hi.m], axis=1),
                    denom=jnp.concatenate([lo.denom, hi.denom], axis=1))


def causal_attention(q, k, v, *, scale: Optional[float] = None,
                     leaf: int = 512) -> jnp.ndarray:
    """Full causal self-attention (training / prefill)."""
    assert q.shape[1] == k.shape[1], "causal path requires aligned q/kv"
    n_kv = k.shape[2]
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    s = q.shape[1]
    # halving recurses while the length stays even; odd lengths fall back
    # to a dense-masked leaf (only reachable for tiny smoke shapes)
    leaf = min(leaf, s)
    qg = _group_q(q, n_kv)
    part = _causal_partial(qg, k, v, scale, leaf)
    return _finalize(part, q.dtype)


def windowed_attention(q, k, v, *, window: int, scale: Optional[float] = None,
                       block_q: int = 512) -> jnp.ndarray:
    """Sliding-window causal attention, O(S * window) FLOPs.

    Each query block of ``block_q`` positions gathers the ``window +
    block_q`` keys ending at its last position (clamped at 0) and masks
    the out-of-range/future entries.
    """
    b, s, hq, hd = q.shape
    n_kv = k.shape[2]
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    block_q = min(block_q, s)
    if s % block_q != 0:
        block_q = s  # irregular smoke shapes: single block
    n_blocks = s // block_q
    span = min(window + block_q, s)

    qg = _group_q(q, n_kv)  # (B,S,Hkv,G,hd)

    def one_block(i):
        q_blk = jax.lax.dynamic_slice_in_dim(qg, i * block_q, block_q, axis=1)
        start = jnp.clip(i * block_q + block_q - span, 0, s - span)
        k_blk = jax.lax.dynamic_slice_in_dim(k, start, span, axis=1)
        v_blk = jax.lax.dynamic_slice_in_dim(v, start, span, axis=1)
        # absolute positions
        q_pos = i * block_q + jnp.arange(block_q)
        k_pos = start + jnp.arange(span)
        scores_mask = (k_pos[None, :] <= q_pos[:, None]) & \
                      (k_pos[None, :] > q_pos[:, None] - window)
        b_, sq, hkv, g, _ = q_blk.shape
        scores = jnp.einsum("bqhgd,bkhd->bqhgk", q_blk.astype(jnp.float32),
                            k_blk.astype(jnp.float32)) * scale
        scores = jnp.where(scores_mask[None, :, None, None, :], scores, NEG_INF)
        m = jnp.max(scores, axis=-1)
        p = jnp.exp(scores - jnp.maximum(m, NEG_INF / 2)[..., None])
        p = jnp.where(scores_mask[None, :, None, None, :], p, 0.0)
        denom = jnp.sum(p, axis=-1)
        out = jnp.einsum("bqhgk,bkhd->bqhgd", p, v_blk.astype(jnp.float32))
        out = out / jnp.maximum(denom, 1e-30)[..., None]
        return out.reshape(b_, sq, hkv * g, hd).astype(q.dtype)

    # lax.map keeps the HLO one-block-sized regardless of S (the 500k
    # decode/prefill path would otherwise unroll S/block_q bodies)
    out = jax.lax.map(one_block, jnp.arange(n_blocks))  # (n, B, bq, H, hd)
    out = jnp.moveaxis(out, 0, 1)
    return out.reshape(b, s, hq, hd)


# --------------------------------------------------------------------------
# KV cache (decode)
# --------------------------------------------------------------------------

def init_cache(batch: int, cache_len: int, n_kv: int, head_dim: int, dtype):
    """A (possibly ring) KV cache for one layer."""
    return {
        "k": jnp.zeros((batch, cache_len, n_kv, head_dim), dtype),
        "v": jnp.zeros((batch, cache_len, n_kv, head_dim), dtype),
    }


def cache_update(cache: dict, k_new: jnp.ndarray, v_new: jnp.ndarray,
                 pos: jnp.ndarray) -> dict:
    """Write one token at ``pos`` (ring indexed by pos % cache_len)."""
    cache_len = cache["k"].shape[1]
    idx = (pos % cache_len).astype(jnp.int32)
    k = jax.lax.dynamic_update_slice_in_dim(cache["k"], k_new, idx, axis=1)
    v = jax.lax.dynamic_update_slice_in_dim(cache["v"], v_new, idx, axis=1)
    return {"k": k, "v": v}


def decode_attention(q, cache: dict, pos: jnp.ndarray,
                     scale: Optional[float] = None) -> jnp.ndarray:
    """Single-token attention against the cache.

    q: (B, 1, Hq, hd); cache k/v: (B, T, Hkv, hd); pos: scalar int32 — the
    absolute position of the current token (cache already updated).
    Valid entries: min(pos + 1, T) slots.
    """
    b, _, hq, hd = q.shape
    t = cache["k"].shape[1]
    n_kv = cache["k"].shape[2]
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    qg = _group_q(q, n_kv)  # (B,1,Hkv,G,hd)
    scores = jnp.einsum("bqhgd,bkhd->bqhgk", qg.astype(jnp.float32),
                        cache["k"].astype(jnp.float32)) * scale
    valid = jnp.arange(t) < jnp.minimum(pos + 1, t)
    scores = jnp.where(valid[None, None, None, None, :], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bqhgk,bkhd->bqhgd", p, cache["v"].astype(jnp.float32))
    return out.reshape(b, 1, hq, hd).astype(q.dtype)
