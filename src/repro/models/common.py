"""Shared building blocks: initializers, RMSNorm, RoPE, embeddings, MLPs.

All modules are pure functions over explicit param dicts; params are
created by ``init_*`` helpers so every model's pytree is plain nested
dicts (checkpointable, aggregatable by the FL layer with zero knowledge
of the architecture).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp


# --------------------------------------------------------------------------
# initializers
# --------------------------------------------------------------------------

def dense_init(key, shape, dtype, scale: Optional[float] = None):
    """Truncated-normal fan-in init (the LLaMA/PaLM convention)."""
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    std = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return (jax.random.truncated_normal(key, -3, 3, shape, jnp.float32) * std).astype(dtype)


def embed_init(key, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


# --------------------------------------------------------------------------
# RMSNorm
# --------------------------------------------------------------------------

def init_rmsnorm(d: int, dtype) -> dict:
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(params: dict, x: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(x.dtype)


# --------------------------------------------------------------------------
# RoPE
# --------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float) -> jnp.ndarray:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
               freqs: Optional[jnp.ndarray] = None,
               mscale: float = 1.0) -> jnp.ndarray:
    """x: (..., S, H, head_dim); positions: broadcastable to (..., S).
    Dim i pairs with dim i + head_dim/2. ``freqs`` replaces the plain
    inverse frequencies (YaRN), ``mscale`` scales cos and sin."""
    head_dim = x.shape[-1]
    if freqs is None:
        freqs = rope_frequencies(head_dim, theta)                # (half,)
    angles = positions[..., None].astype(jnp.float32) * freqs    # (..., S, half)
    cos = jnp.cos(angles)[..., None, :] * mscale                 # (..., S, 1, half)
    sin = jnp.sin(angles)[..., None, :] * mscale
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_frequencies(head_dim: int, theta: float, scaling) -> jnp.ndarray:
    """YaRN's blend of interpolated and original inverse frequencies
    (``scaling``: a ``configs.base.YarnScaling``): dims that rotate fewer
    than ``beta_slow`` times over the original context are divided by
    ``factor``, those over ``beta_fast`` kept, a linear ramp between."""
    def correction_dim(rotations: float) -> float:
        return (head_dim * math.log(scaling.original_max_position
                                    / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(scaling.beta_fast)), 0)
    high = min(math.ceil(correction_dim(scaling.beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    extra = rope_frequencies(head_dim, theta)
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp
    return extra / scaling.factor * (1.0 - keep) + extra * keep


# --------------------------------------------------------------------------
# Embedding / unembedding (padded vocab)
# --------------------------------------------------------------------------

def init_embedding(key, vocab_padded: int, d_model: int, dtype) -> dict:
    return {"table": embed_init(key, (vocab_padded, d_model), dtype)}


def embed(params: dict, tokens: jnp.ndarray) -> jnp.ndarray:
    return jnp.take(params["table"], tokens, axis=0)


def unembed(params: dict, x: jnp.ndarray) -> jnp.ndarray:
    """Tied unembedding: logits over the padded vocab."""
    return jnp.einsum("...d,vd->...v", x, params["table"])


def init_unembed(key, vocab_padded: int, d_model: int, dtype) -> dict:
    return {"proj": dense_init(key, (d_model, vocab_padded), dtype)}


def unembed_untied(params: dict, x: jnp.ndarray) -> jnp.ndarray:
    return jnp.einsum("...d,dv->...v", x, params["proj"])


# --------------------------------------------------------------------------
# MLP variants
# --------------------------------------------------------------------------

def init_swiglu(key, d_model: int, d_ff: int, dtype) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(k1, (d_model, d_ff), dtype),
        "w_up": dense_init(k2, (d_model, d_ff), dtype),
        "w_down": dense_init(k3, (d_ff, d_model), dtype),
    }


def swiglu(params: dict, x: jnp.ndarray) -> jnp.ndarray:
    gate = jax.nn.silu(jnp.einsum("...d,df->...f", x, params["w_gate"]))
    up = jnp.einsum("...d,df->...f", x, params["w_up"])
    return jnp.einsum("...f,fd->...d", gate * up, params["w_down"])


def init_geglu(key, d_model: int, d_ff: int, dtype) -> dict:
    return init_swiglu(key, d_model, d_ff, dtype)


def geglu(params: dict, x: jnp.ndarray) -> jnp.ndarray:
    gate = jax.nn.gelu(jnp.einsum("...d,df->...f", x, params["w_gate"]))
    up = jnp.einsum("...d,df->...f", x, params["w_up"])
    return jnp.einsum("...f,fd->...d", gate * up, params["w_down"])


def init_mlp(key, d_model: int, d_ff: int, dtype) -> dict:
    k1, k2 = jax.random.split(key)
    return {
        "w_up": dense_init(k1, (d_model, d_ff), dtype),
        "w_down": dense_init(k2, (d_ff, d_model), dtype),
    }


def gelu_mlp(params: dict, x: jnp.ndarray) -> jnp.ndarray:
    h = jax.nn.gelu(jnp.einsum("...d,df->...f", x, params["w_up"]))
    return jnp.einsum("...f,fd->...d", h, params["w_down"])


# --------------------------------------------------------------------------
# Losses
# --------------------------------------------------------------------------

def softmax_xent(logits: jnp.ndarray, labels: jnp.ndarray,
                 vocab_size: int, mask: Optional[jnp.ndarray] = None):
    """Cross-entropy with padded-vocab masking. logits (..., V_pad).

    Sharding-friendly formulation: the vocab dim is model-sharded for
    every zoo arch, and both ``.at[slice].set`` and ``take_along_axis``
    over a sharded dim make GSPMD all-gather the FULL logits (measured
    67 GB/device for a 256k vocab at 4k seq — EXPERIMENTS.md §Perf it.5).
    Instead: iota-compare masking and a one-hot dot — pure elementwise +
    reductions, which lower to small psums over the model axis.
    """
    v_pad = logits.shape[-1]
    logits = logits.astype(jnp.float32)
    vocab_ids = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                         logits.ndim - 1)
    if v_pad > vocab_size:
        logits = jnp.where(vocab_ids < vocab_size, logits, -1e9)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.sum(jnp.where(vocab_ids == labels[..., None], logits, 0.0),
                   axis=-1)
    nll = logz - gold
    if mask is not None:
        mask = mask.astype(jnp.float32)
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
