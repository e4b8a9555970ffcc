"""Pallas TPU kernel: blocked gated linear recurrence (RG-LRU scan).

RecurrentGemma's recurrence h_t = a_t * h_{t-1} + u_t is the classic
bandwidth-bound sequential hot spot: on TPU the win is keeping the
running state h in VMEM while streaming (a, u) time-blocks HBM->VMEM,
never round-tripping the state.

Schedule: grid = (batch, d_blocks, t_blocks) with the time axis innermost
and sequential ("arbitrary"); each step holds an (block_t, block_d) tile
of a and u in VMEM plus the (block_d,) state carry in VMEM scratch. The
in-tile recurrence is a **log-depth Blelloch-style composition**: the
affine maps (a, u) compose associatively,
    (a2, u2) o (a1, u1) = (a2*a1, a2*u1 + u2),
so the tile scan runs in log2(block_t) VPU sweeps instead of block_t
serial steps — the TPU-native reformulation of the elementwise scan
(a GPU implementation would use warp shuffles; here the vector unit
sweeps whole (block_t, block_d) tiles).

Validated against ``ref.rglru_scan_ref`` with interpret=True.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_T = 256
DEFAULT_BLOCK_D = 256


def _tile_scan(a: jnp.ndarray, u: jnp.ndarray):
    """Inclusive associative scan of the affine recurrence over axis 0.

    a, u: (T, D) f32. Returns (A, U) where U[t] = h_t given h_{-1}=0 and
    A[t] = prod_{i<=t} a_i (the factor multiplying the incoming state).
    Log-depth: T must be a power of two.
    """
    t = a.shape[0]
    A, U = a, u
    shift = 1
    while shift < t:
        # compose each element with the element `shift` before it
        A_prev = jnp.concatenate([jnp.ones_like(A[:shift]), A[:-shift]], axis=0)
        U_prev = jnp.concatenate([jnp.zeros_like(U[:shift]), U[:-shift]], axis=0)
        mask = (jax.lax.broadcasted_iota(jnp.int32, A.shape, 0) >= shift)
        A_new = jnp.where(mask, A * A_prev, A)
        U_new = jnp.where(mask, A * U_prev + U, U)
        A, U = A_new, U_new
        shift *= 2
    return A, U


def _rglru_kernel(a_ref, u_ref, o_ref, h_ref, *, n_t_blocks: int):
    it = pl.program_id(2)

    @pl.when(it == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    a = a_ref[0].astype(jnp.float32)          # (block_t, block_d)
    u = u_ref[0].astype(jnp.float32)
    A, U = _tile_scan(a, u)                    # log-depth in-tile scan
    h_in = h_ref[...]                          # (block_d,)
    h = U + A * h_in[None, :]                  # inject carried state
    o_ref[0] = h.astype(o_ref.dtype)
    h_ref[...] = h[-1]


@functools.partial(jax.jit, static_argnames=("block_t", "block_d",
                                             "interpret"))
def rglru_scan_pallas(a: jnp.ndarray, u: jnp.ndarray,
                      block_t: int = DEFAULT_BLOCK_T,
                      block_d: int = DEFAULT_BLOCK_D,
                      interpret: bool = False) -> jnp.ndarray:
    """a, u (B, T, D) -> h (B, T, D) with h_t = a_t*h_{t-1} + u_t, h_{-1}=0.

    T must divide block_t (ops.py pads); block_t must be a power of two.
    """
    b, t, d = a.shape
    block_t = min(block_t, t)
    block_d = min(block_d, d)
    assert block_t & (block_t - 1) == 0, "block_t must be a power of two"
    assert t % block_t == 0 and d % block_d == 0
    n_t, n_d = t // block_t, d // block_d

    grid = (b, n_d, n_t)
    kernel = functools.partial(_rglru_kernel, n_t_blocks=n_t)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_t, block_d),
                         lambda ib, idd, it: (ib, it, idd)),
            pl.BlockSpec((1, block_t, block_d),
                         lambda ib, idd, it: (ib, it, idd)),
        ],
        out_specs=pl.BlockSpec((1, block_t, block_d),
                               lambda ib, idd, it: (ib, it, idd)),
        out_shape=jax.ShapeDtypeStruct(a.shape, a.dtype),
        scratch_shapes=[pltpu.VMEM((block_d,), jnp.float32)],  # state carry
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, u)
