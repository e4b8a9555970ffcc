"""Pallas TPU kernel: blocked causal / sliding-window GQA flash attention.

The per-client training & prefill hot spot. TPU-native schedule:

* grid = (batch, q_head, q_block, kv_block); the kv dimension is the
  innermost, sequential ("arbitrary") axis — the online-softmax carry
  (acc, m, l) lives in VMEM scratch across kv steps, exactly the
  HBM->VMEM streaming pattern the MXU wants. Block sizes default to
  (128, 128): multiples of the 128-lane MXU tile and of the 8x128 VREG.
* causal + sliding-window masking is applied per (q_block, kv_block)
  tile with an iota comparison; whole tiles strictly above the diagonal
  (or left of the window) are *skipped* via ``pl.when`` so the kernel
  does the exact S^2/2 (or S*window) FLOPs — matching the exact-FLOP
  jnp oracle in ``repro.models.attention``.
* GQA: the q-head grid axis maps to kv head ``h // group`` in the k/v
  BlockSpec index_maps — no repeat/materialization of kv heads.

Validated on CPU with interpret=True against ``ref.flash_attention_ref``
(tests/test_kernels.py sweeps shapes, dtypes, window sizes, GQA ratios).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_KV = 128


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, causal: bool, window: Optional[int],
                  block_q: int, block_kv: int, n_kv_blocks: int,
                  kv_len: Optional[int]):
    """One (q_block, kv_block) step of the online-softmax recurrence."""
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = iq * block_q
    k_start = ik * block_kv

    # tile-level skip: causal => skip tiles fully above the diagonal;
    # window => skip tiles fully left of the window of the *last* query row
    run = jnp.bool_(True)
    if causal:
        run = run & (k_start <= q_start + block_q - 1)
    if window is not None:
        run = run & (k_start + block_kv - 1 > q_start - window)

    @pl.when(run)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)      # (block_q, hd)
        k = k_ref[0, 0].astype(jnp.float32)      # (block_kv, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale

        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = jnp.ones_like(s, dtype=jnp.bool_)
        if causal:
            mask = mask & (k_pos <= q_pos)
        if window is not None:
            mask = mask & (k_pos > q_pos - window)
        if kv_len is not None:
            mask = mask & (k_pos < kv_len)  # exclude padded keys
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                       # (block_q,)
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        # guard fully-masked rows (leading causal rows of the first tile)
        m_safe = jnp.maximum(m_new, NEG_INF / 2)
        p = jnp.exp(s - m_safe[:, None])
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_safe)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
        acc_ref[...] = (acc_ref[...] * alpha[:, None]
                        + jnp.dot(p, v, preferred_element_type=jnp.float32))
        m_ref[...] = m_new

    @pl.when(ik == n_kv_blocks - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "scale", "block_q", "block_kv",
                     "interpret", "kv_len"))
def flash_attention_pallas(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                           causal: bool = True,
                           window: Optional[int] = None,
                           scale: Optional[float] = None,
                           block_q: int = DEFAULT_BLOCK_Q,
                           block_kv: int = DEFAULT_BLOCK_KV,
                           interpret: bool = False,
                           kv_len: Optional[int] = None) -> jnp.ndarray:
    """q (B, Hq, S, hd); k, v (B, Hkv, S, hd) -> (B, Hq, S, hd).

    Hq must be a multiple of Hkv (GQA). S must divide by the block sizes
    (the ops.py wrapper pads).
    """
    b, hq, s, hd = q.shape
    hkv = k.shape[1]
    assert hq % hkv == 0, "GQA requires Hq % Hkv == 0"
    group = hq // hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    block_q = min(block_q, s)
    block_kv = min(block_kv, s)
    assert s % block_q == 0 and s % block_kv == 0, \
        f"S={s} must divide block sizes ({block_q},{block_kv})"
    n_q, n_kv_blocks = s // block_q, s // block_kv

    grid = (b, hq, n_q, n_kv_blocks)
    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_kv=block_kv, n_kv_blocks=n_kv_blocks,
        kv_len=kv_len)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd),
                         lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, block_kv, hd),
                         lambda ib, ih, iq, ik: (ib, ih // group, ik, 0)),
            pl.BlockSpec((1, 1, block_kv, hd),
                         lambda ib, ih, iq, ik: (ib, ih // group, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd),
                               lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            # VMEM carries for the online softmax across kv steps
            pltpu.VMEM((block_q, hd), jnp.float32),   # acc
            pltpu.VMEM((block_q,), jnp.float32),      # m (running max)
            pltpu.VMEM((block_q,), jnp.float32),      # l (running denom)
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
