"""Mixture-of-Experts FFN with expert-parallel sharding.

Routing is token-choice top-k (softmax over experts, keep k, renormalize)
followed by per-expert capacity truncation — Switch-style token dropping
with capacity_factor slack. The expert compute is organized
**expert-parallel over the ``model`` mesh axis** via an explicit
``shard_map`` island:

  * activations arrive data-sharded and model-replicated (the layout they
    already have between attention and FFN under megatron-style TP);
  * each model shard owns E/model_size experts and serves *all* local
    tokens routed to them (local gather of at most ``capacity`` tokens per
    expert — static shapes, MXU-friendly `(E_local, C, D) x (E_local, D, F)`
    einsums);
  * partial outputs are summed with one ``psum`` over the model axis —
    the EP combine. Collective volume per layer = T_local x D, the same
    as one TP all-reduce, with zero all-to-all of expert weights.

This keeps compiled FLOPs proportional to *active* experts
(T * k * capacity_factor), so the roofline compute term reflects the
a22b active-parameter cost rather than the 235b total — exactly the MoE
accounting the analysis needs.

On a single device (CPU smoke tests) the same math runs without the
shard_map wrapper (E_local == E, no psum).

A config with ``capacity_factor=None`` takes the dropless held-expert
path instead (:func:`held_moe_ffn`, DeepSeekMoE): the router scores all
``n_experts``, the layer holds ``n_held`` of them, and computes every
token-expert pair routed to a held expert as a grouped matmul over the
held experts (tokens sorted by expert, no capacity), plus the shared
experts. On one chip of an expert-parallel deployment this is the
chip's part of the layer's output, without the exchange.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import MoEConfig
from repro.models.common import dense_init, init_swiglu, swiglu
from repro.models.sharding import ShardingPolicy


def init_moe(key, d_model: int, cfg: MoEConfig, dtype) -> dict:
    kr, k1, k2, k3, ks = jax.random.split(key, 5)
    e, f = cfg.held, cfg.d_ff_expert
    params = {
        "router": dense_init(kr, (d_model, cfg.n_experts), jnp.float32),
        "w_gate": dense_init(k1, (e, d_model, f), dtype),
        "w_up": dense_init(k2, (e, d_model, f), dtype),
        "w_down": dense_init(k3, (e, f, d_model), dtype),
    }
    if cfg.n_shared_experts:
        params["shared"] = init_swiglu(
            ks, d_model, cfg.n_shared_experts * f, dtype)
    return params


def _route(x2d: jnp.ndarray, router: jnp.ndarray, top_k: int):
    """Token-choice routing. x2d: (T, D). Returns sparse gates (T, E) and
    the Switch load-balance auxiliary loss."""
    logits = jnp.einsum("td,de->te", x2d.astype(jnp.float32), router)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_i = jax.lax.top_k(probs, top_k)                  # (T, k)
    top_w = top_w / jnp.maximum(jnp.sum(top_w, -1, keepdims=True), 1e-9)
    t = x2d.shape[0]
    gates = jnp.zeros_like(probs)
    gates = gates.at[jnp.arange(t)[:, None], top_i].set(top_w)  # (T, E) sparse
    # Switch aux loss: E * sum_e (fraction of tokens to e) * (mean prob of e)
    e = probs.shape[-1]
    density = jnp.mean((gates > 0).astype(jnp.float32), axis=0)
    mean_prob = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(density * mean_prob)
    return gates, aux


def _expert_compute(x2d: jnp.ndarray, gates: jnp.ndarray,
                    w_gate, w_up, w_down, capacity: int) -> jnp.ndarray:
    """Capacity-gather expert FFN over the local expert slice.

    x2d (T, D); gates (T, E_local); weights (E_local, D, F)/(E_local, F, D).
    Per expert: take the top-``capacity`` tokens by gate weight (tokens
    over capacity are dropped, Switch-style), run the gated FFN, and
    scatter-add weighted outputs back.
    """
    t, d = x2d.shape
    cap = min(capacity, t)
    # (E_local, C) token indices per expert, by gate magnitude
    gw, gi = jax.lax.top_k(gates.T, cap)                        # (E_local, C)
    xe = x2d[gi]                                                # (E_local, C, D)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, w_gate))
    h = h * jnp.einsum("ecd,edf->ecf", xe, w_up)
    ye = jnp.einsum("ecf,efd->ecd", h, w_down)                  # (E_local, C, D)
    ye = ye * gw[..., None].astype(ye.dtype)                    # gate weighting
    out = jnp.zeros((t, d), ye.dtype)
    out = out.at[gi.reshape(-1)].add(ye.reshape(-1, d))
    return out


def _moe_ffn_ep2d(params: dict, x2d: jnp.ndarray, gates: jnp.ndarray,
                  cfg: MoEConfig, policy: ShardingPolicy) -> jnp.ndarray:
    """Serving path: experts 2-D-sharded at rest (E over the data axis,
    F over the model axis). No weight movement at all — the token batch
    (tiny at decode) is what travels: one gather of x2d to the expert
    rows and one all-reduce of the (T, D) output. Replaces the per-step
    FSDP weight gathers that dominated the decode collective term."""
    from jax.sharding import NamedSharding

    mesh, dax, m = policy.mesh, policy.ep2d_axis, policy.model_axis
    t, d = x2d.shape
    e = cfg.n_experts
    cap = max(1, min(t, math.ceil(t * cfg.top_k * cfg.capacity_factor / e)))

    def wsc(v, spec):
        return jax.lax.with_sharding_constraint(v, NamedSharding(mesh, spec))

    gw, gi = jax.lax.top_k(gates.T, cap)                  # (E, C)
    gi = wsc(gi, P(dax, None))
    gw = wsc(gw, P(dax, None))
    xe = wsc(x2d[gi], P(dax, None, None))                 # (E, C, D)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, params["w_gate"]))
    h = h * jnp.einsum("ecd,edf->ecf", xe, params["w_up"])
    h = wsc(h, P(dax, None, m))                           # (E, C, F)
    ye = jnp.einsum("ecf,efd->ecd", h, params["w_down"])  # contract F -> AR
    ye = wsc(ye * gw[..., None].astype(ye.dtype), P(dax, None, None))
    out = jnp.zeros((t, d), ye.dtype)
    out = out.at[gi.reshape(-1)].add(ye.reshape(-1, d))   # (T, D), ~MBs
    return out


def moe_ffn(params: dict, x: jnp.ndarray, cfg: MoEConfig,
            policy: ShardingPolicy, mask: Optional[jnp.ndarray] = None):
    """x: (B, S, D) -> (out (B, S, D), aux_loss scalar).

    ``mask`` (S,) bool marks real (non-pad) positions: pad tokens get
    zero gates so they never displace real tokens from expert capacity.
    """
    b, s, d = x.shape
    x2d = x.reshape(b * s, d)
    gates, aux = _route(x2d, params["router"], cfg.top_k)
    if mask is not None:
        m2d = jnp.broadcast_to(mask[None, :], (b, s)).reshape(b * s)
        gates = gates * m2d[:, None].astype(gates.dtype)

    e = cfg.n_experts
    if policy.ep2d_axis is not None:
        out = _moe_ffn_ep2d(params, x2d, gates.astype(x.dtype), cfg, policy)
        return out.reshape(b, s, d).astype(x.dtype), aux
    model_axis = policy.model_axis
    # the EP island assumes data-sharded activations; under the FL replica
    # path (batch_axes=None, client-vmapped) fall back to the dense path —
    # GSPMD still expert-shards it via the param specs
    ep = (policy.mesh is not None and model_axis is not None
          and e % policy.model_size == 0 and policy.model_size > 1
          and policy.batch_axes is not None)

    if not ep:
        t_eff = max(x2d.shape[0] // max(policy.batch_size_divisor, 1), 1)
        capacity = max(1, math.ceil(t_eff * cfg.top_k * cfg.capacity_factor / e))
        out = _expert_compute(x2d, gates, params["w_gate"], params["w_up"],
                              params["w_down"], capacity)
        return out.reshape(b, s, d).astype(x.dtype), aux

    batch_axes = policy.batch_axes or ()
    div = max(policy.batch_size_divisor, 1)
    if x2d.shape[0] % div != 0:
        # e.g. single-sequence decode (T=1): tokens replicate over the
        # data axes; each model shard still serves only its local experts
        batch_axes = ()
        div = 1
    t_local = max(x2d.shape[0] // div, 1)
    capacity = max(1, math.ceil(t_local * cfg.top_k * cfg.capacity_factor / e))

    def shard_fn(x2d_l, gates_l, w_gate_l, w_up_l, w_down_l):
        # FSDP fragments of expert weights are gathered here, making the
        # ZeRO-3 per-layer gather explicit inside the EP island.
        if policy.fsdp_axes:
            for ax in policy.fsdp_axes:
                w_gate_l = jax.lax.all_gather(w_gate_l, ax, axis=1, tiled=True)
                w_up_l = jax.lax.all_gather(w_up_l, ax, axis=1, tiled=True)
                w_down_l = jax.lax.all_gather(w_down_l, ax, axis=2, tiled=True)
        out_l = _expert_compute(x2d_l, gates_l, w_gate_l, w_up_l, w_down_l,
                                capacity)
        return jax.lax.psum(out_l, model_axis)

    batch_entry = batch_axes if len(batch_axes) > 1 else (
        batch_axes[0] if batch_axes else None)
    x_spec = P(batch_entry, None)
    gates_spec = P(batch_entry, model_axis)
    fsdp = (policy.fsdp_axes[0] if policy.fsdp_axes and
            len(policy.fsdp_axes) == 1 else
            (policy.fsdp_axes if policy.fsdp_axes else None))
    w_in_spec = P(model_axis, fsdp, None)     # (E, D, F): E over model, D fsdp
    w_out_spec = P(model_axis, None, fsdp)    # (E, F, D)

    out2d = jax.shard_map(
        shard_fn,
        mesh=policy.mesh,
        in_specs=(x_spec, gates_spec, w_in_spec, w_in_spec, w_out_spec),
        out_specs=x_spec,
        check_vma=False,
    )(x2d, gates.astype(x.dtype), params["w_gate"], params["w_up"],
      params["w_down"])
    return out2d.reshape(b, s, d).astype(x.dtype), aux


# ---------------------------------------------------------------------------
# dropless held-expert path (DeepSeekMoE)
# ---------------------------------------------------------------------------

def route_topk(x2d: jnp.ndarray, router: jnp.ndarray, cfg: MoEConfig):
    """Softmax scoring over every expert and greedy top-k, in float32.

    Returns ``(probs (T, E), weights (T, k), experts (T, k))``; the
    weights are the top-k probabilities as they are, neither renormalized
    nor scaled (DeepSeek-V2's ``norm_topk_prob`` false,
    ``routed_scaling_factor`` 1)."""
    logits = jnp.einsum("td,de->te", x2d.astype(jnp.float32),
                        router.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_i = jax.lax.top_k(probs, cfg.top_k)
    return probs, top_w, top_i


def seq_balance_loss(probs: jnp.ndarray, top_i: jnp.ndarray,
                     n_seqs: int) -> jnp.ndarray:
    """DeepSeek-V2's sequence-level balance loss (§2.2.3), unweighted:
    per sequence, sum_e f_e * P_e with f_e the expert's share of the
    sequence's top-k picks times E/k and P_e its mean probability;
    averaged over the sequences."""
    t, e = probs.shape
    k = top_i.shape[-1]
    s = t // n_seqs
    picks = jax.nn.one_hot(top_i.reshape(n_seqs, s * k), e,
                           dtype=jnp.float32).sum(axis=1)     # (B, E)
    f = picks / (s * k / e)
    p = probs.reshape(n_seqs, s, e).mean(axis=1)
    return jnp.mean(jnp.sum(f * p, axis=-1))


def _gmm_tiling(m: int, k: int, n: int) -> tuple:
    """Tiles of the TPU grouped matmul: 512 rows, the contracted dim in
    512s (whole when it is not a multiple), the output dim whole up to
    1,536 columns, else in 512s."""
    tm = 512 if m % 512 == 0 else 128
    tk = 512 if k % 512 == 0 else k
    tn = n if n <= 1536 else 512
    return tm, tk, tn


def grouped_matmul(lhs: jnp.ndarray, rhs: jnp.ndarray,
                   sizes: jnp.ndarray) -> jnp.ndarray:
    """``lhs`` (M, K) rows sorted by group, ``rhs`` (G, K, N), ``sizes``
    (G + 1,) rows per group with the last entry counting the rows that
    belong to no held group (they sit at the end). Returns (M, N) in
    ``lhs``'s dtype, accumulated in float32: each held group's rows times
    its matrix, zeros after.

    On a TPU this is the megablox ``gmm`` kernel (its backward is
    ``gmm``/``tgmm``), which computes only the held groups' tiles;
    elsewhere ``lax.ragged_dot``."""
    if jax.default_backend() == "tpu":
        from jax.experimental.pallas.ops.tpu.megablox import gmm
        return gmm(lhs, rhs, sizes, lhs.dtype, _gmm_tiling)
    # rows past the held groups are left undefined by ragged_dot (and by
    # its transpose): zero them on the way in and out
    valid = (jnp.arange(lhs.shape[0]) < jnp.sum(sizes[:-1]))[:, None]
    out = jax.lax.ragged_dot(jnp.where(valid, lhs, 0), rhs, sizes[:-1],
                             preferred_element_type=jnp.float32)
    return jnp.where(valid, out, 0.0).astype(lhs.dtype)


def held_experts(x2d: jnp.ndarray, top_w: jnp.ndarray, top_i: jnp.ndarray,
                 params: dict, cfg: MoEConfig):
    """The held experts' gated outputs for the token-expert pairs routed
    to them, weighted by their gates and summed per token; no pair is
    dropped. Returns ``(out (T, D) float32, pairs computed)``."""
    t, d = x2d.shape
    k, n = top_i.shape[-1], cfg.held
    local = (top_i - cfg.expert_shard * n).reshape(-1)          # (T*k,)
    held = (local >= 0) & (local < n)
    key = jnp.where(held, local, n)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.bincount(key, length=n + 1).astype(jnp.int32)
    rows = order // k                                           # token ids
    xs = x2d[rows]
    dt = x2d.dtype
    h = jax.nn.silu(grouped_matmul(xs, params["w_gate"].astype(dt), sizes))
    h = h * grouped_matmul(xs, params["w_up"].astype(dt), sizes)
    y = grouped_matmul(h, params["w_down"].astype(dt), sizes)
    w = jnp.where(held, top_w.reshape(-1), 0.0)[order]
    out = jnp.zeros((t, d), jnp.float32).at[rows].add(
        y.astype(jnp.float32) * w[:, None])
    return out, jnp.sum(sizes[:n])


def held_moe_ffn(params: dict, x: jnp.ndarray, cfg: MoEConfig,
                 mask: Optional[jnp.ndarray] = None):
    """x: (B, S, D) -> (out (B, S, D), sequence-level balance loss,
    pairs computed on the held experts). Pad positions (``mask`` False)
    are routed nowhere."""
    b, s, d = x.shape
    x2d = x.reshape(b * s, d)
    probs, top_w, top_i = route_topk(x2d, params["router"], cfg)
    aux = seq_balance_loss(probs, top_i, b)
    if mask is not None:
        real = jnp.broadcast_to(mask[None, :], (b, s)).reshape(b * s)
        top_i = jnp.where(real[:, None], top_i, cfg.n_experts)
    out, pairs = held_experts(x2d, top_w, top_i, params, cfg)
    if "shared" in params:
        shared = jax.tree.map(lambda w: w.astype(x2d.dtype), params["shared"])
        out = out + swiglu(shared, x2d).astype(jnp.float32)
    return out.reshape(b, s, d).astype(x.dtype), aux, pairs


def moe_spec(path: str, shape, policy: ShardingPolicy,
             stacked: bool = True) -> Optional[P]:
    """PartitionSpec rule for MoE param leaves (None if not a MoE leaf).

    Expert tensors: E over model, D over fsdp. Router: replicated.
    ``stacked`` => leading layer dim.
    """
    lead = (None,) if stacked else ()
    m, f = policy.model_axis, policy.fsdp_axes
    f = f[0] if f and len(f) == 1 else f
    if path.endswith("router"):
        return P(*lead, None, None)
    if policy.ep2d_axis is not None:
        # serving layout: E over data, F over model — no gathers at use
        dax = policy.ep2d_axis
        if path.endswith(("w_gate", "w_up")) and len(shape) == len(lead) + 3:
            return P(*lead, dax, None, m)
        if path.endswith("w_down") and len(shape) == len(lead) + 3:
            return P(*lead, dax, m, None)
    if path.endswith(("w_gate", "w_up")) and len(shape) == len(lead) + 3:
        return P(*lead, m, f, None)
    if path.endswith("w_down") and len(shape) == len(lead) + 3:
        return P(*lead, m, None, f)
    return None
