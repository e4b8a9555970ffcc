"""Decoder-only transformer: the dense, MoE and VLM families.

One block implementation serves all three (the MoE family swaps the FFN
for the expert-parallel ``moe_ffn``; the VLM family prepends stub patch
embeddings and pads the sequence to a power of two so the exact-FLOP
causal decomposition applies). A config with ``mla`` uses multi-head
latent attention with YaRN rotary keys, and ``first_k_dense`` leading
dense layers run before the MoE stack (DeepSeek-V2); such a model is
built for training only (no prefill or decode).

Layers are stacked and driven by ``lax.scan`` so the HLO contains one
layer body regardless of depth — Qwen3's 94 layers lower in seconds, and
per-layer FSDP gathers appear once inside the loop (ZeRO-3 schedule).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import attention as attn_lib, common
from repro.models.api import Model
from repro.models.moe import held_moe_ffn, init_moe, moe_ffn, moe_spec
from repro.models.sharding import UNSHARDED, ShardingPolicy, shard_hint


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------

def _init_attn(key, cfg: ModelConfig, dtype) -> dict:
    if cfg.mla is not None:
        return _init_mla(key, cfg, dtype)
    kq, kk, kv, ko = jax.random.split(key, 4)
    hd = cfg.resolved_head_dim
    return {
        "wq": common.dense_init(kq, (cfg.d_model, cfg.n_heads * hd), dtype),
        "wk": common.dense_init(kk, (cfg.d_model, cfg.n_kv_heads * hd), dtype),
        "wv": common.dense_init(kv, (cfg.d_model, cfg.n_kv_heads * hd), dtype),
        "wo": common.dense_init(ko, (cfg.n_heads * hd, cfg.d_model), dtype),
    }


def _init_mla(key, cfg: ModelConfig, dtype) -> dict:
    m, h = cfg.mla, cfg.n_heads
    kq, ka, kb, ko = jax.random.split(key, 4)
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    return {
        "wq": common.dense_init(kq, (cfg.d_model, h * qk), dtype),
        "wkv_a": common.dense_init(
            ka, (cfg.d_model, m.kv_lora_rank + m.qk_rope_head_dim), dtype),
        "kv_norm": common.init_rmsnorm(m.kv_lora_rank, dtype),
        "wkv_b": common.dense_init(
            kb, (m.kv_lora_rank, h * (m.qk_nope_head_dim + m.v_head_dim)),
            dtype),
        "wo": common.dense_init(ko, (h * m.v_head_dim, cfg.d_model), dtype),
    }


def _init_layer(key, cfg: ModelConfig, dtype, dense: bool = False) -> dict:
    ka, kf = jax.random.split(key)
    layer = {
        "ln1": common.init_rmsnorm(cfg.d_model, dtype),
        "ln2": common.init_rmsnorm(cfg.d_model, dtype),
        "attn": _init_attn(ka, cfg, dtype),
    }
    if cfg.moe is not None and not dense:
        layer["moe"] = init_moe(kf, cfg.d_model, cfg.moe, dtype)
    else:
        layer["ffn"] = common.init_swiglu(kf, cfg.d_model, cfg.d_ff, dtype)
    return layer


def init_decoder_params(rng, cfg: ModelConfig) -> dict:
    dtype = jnp.dtype(cfg.param_dtype)
    k_emb, k_layers, k_out = jax.random.split(rng, 3)
    layer_keys = jax.random.split(k_layers, cfg.n_layers)
    n_dense = cfg.first_k_dense
    layers = jax.vmap(lambda k: _init_layer(k, cfg, dtype))(
        layer_keys[n_dense:])
    params = {
        "embed": common.init_embedding(k_emb, cfg.padded_vocab, cfg.d_model, dtype),
        "layers": layers,
        "ln_f": common.init_rmsnorm(cfg.d_model, dtype),
    }
    if n_dense:
        params["dense_layers"] = jax.vmap(
            lambda k: _init_layer(k, cfg, dtype, dense=True))(
                layer_keys[:n_dense])
    if not cfg.tie_embeddings:
        params["lm_head"] = common.init_unembed(
            k_out, cfg.padded_vocab, cfg.d_model, dtype)
    return params


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------

def attention_block(layer_attn: dict, x: jnp.ndarray, cfg: ModelConfig,
                    policy: ShardingPolicy, positions: jnp.ndarray,
                    window: Optional[int]) -> jnp.ndarray:
    """Self-attention over the full (already-embedded) sequence."""
    b, s, _ = x.shape
    hd = cfg.resolved_head_dim
    dt = jnp.dtype(cfg.dtype)
    xc = x.astype(dt)
    q = jnp.einsum("bsd,dh->bsh", xc, layer_attn["wq"].astype(dt))
    k = jnp.einsum("bsd,dh->bsh", xc, layer_attn["wk"].astype(dt))
    v = jnp.einsum("bsd,dh->bsh", xc, layer_attn["wv"].astype(dt))
    q = q.reshape(b, s, cfg.n_heads, hd)
    k = k.reshape(b, s, cfg.n_kv_heads, hd)
    v = v.reshape(b, s, cfg.n_kv_heads, hd)
    q = common.apply_rope(q, positions, cfg.rope_theta)
    k = common.apply_rope(k, positions, cfg.rope_theta)
    if window is not None and window < s:
        o = attn_lib.windowed_attention(q, k, v, window=window)
    else:
        o = attn_lib.causal_attention(q, k, v)
    o = o.reshape(b, s, cfg.n_heads * hd)
    return jnp.einsum("bsh,hd->bsd", o, layer_attn["wo"].astype(dt)).astype(x.dtype)


def rope_tables(cfg: ModelConfig, head_dim: int) -> tuple:
    """(inverse frequencies or None, cos/sin scale) of the rotary dims."""
    y = cfg.rope_scaling
    if y is None:
        return None, 1.0
    return (common.yarn_frequencies(head_dim, cfg.rope_theta, y),
            common.yarn_mscale(y.factor, y.mscale)
            / common.yarn_mscale(y.factor, y.mscale_all_dim))


def mla_softmax_scale(cfg: ModelConfig) -> float:
    """(qk_nope + qk_rope)^-1/2, times YaRN's mscale(all dims) squared."""
    m, y = cfg.mla, cfg.rope_scaling
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    if y is not None and y.mscale_all_dim:
        scale *= common.yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return scale


def mla_attention_block(p: dict, x: jnp.ndarray, cfg: ModelConfig,
                        positions: jnp.ndarray) -> jnp.ndarray:
    """Multi-head latent attention (DeepSeek-V2 §2.1), no query LoRA:
    q = W_q x split into nope and rope dims per head; [c_kv; k_rope] =
    W_kva x; [k_nope; v] = W_kvb RMSNorm(c_kv); one rotary key shared by
    all heads; scores over qk_nope + qk_rope dims, values of v_head_dim."""
    m, h = cfg.mla, cfg.n_heads
    b, s, _ = x.shape
    dn, dr, dv, r = (m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim,
                     m.kv_lora_rank)
    dt = jnp.dtype(cfg.dtype)
    xc = x.astype(dt)
    q = jnp.einsum("bsd,dh->bsh", xc, p["wq"].astype(dt)).reshape(
        b, s, h, dn + dr)
    kva = jnp.einsum("bsd,dh->bsh", xc, p["wkv_a"].astype(dt))
    c_kv = common.rmsnorm(p["kv_norm"], kva[..., :r], cfg.norm_eps)
    kv = jnp.einsum("bsr,rh->bsh", c_kv, p["wkv_b"].astype(dt)).reshape(
        b, s, h, dn + dv)
    freqs, mscale = rope_tables(cfg, dr)
    q_rope = common.apply_rope(q[..., dn:], positions, cfg.rope_theta,
                               freqs, mscale)
    k_rope = common.apply_rope(kva[..., None, r:], positions,
                               cfg.rope_theta, freqs, mscale)
    q = jnp.concatenate([q[..., :dn], q_rope], axis=-1)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_rope, (b, s, h, dr))], axis=-1)
    o = attn_lib.causal_attention(q, k, kv[..., dn:],
                                  scale=mla_softmax_scale(cfg))
    o = o.reshape(b, s, h * dv)
    return jnp.einsum("bsh,hd->bsd", o, p["wo"].astype(dt)).astype(x.dtype)


def make_block_fn(cfg: ModelConfig, policy: ShardingPolicy,
                  window: Optional[int], n_real: Optional[int] = None,
                  dense: bool = False):
    """(carry=(x, aux), layer_params) -> ((x, aux), held).

    ``n_real``: number of real (non-pad) positions — pads are masked out
    of MoE routing so they cannot consume expert capacity. ``dense``: the
    block of a leading dense layer. ``held`` is the number of
    token-expert pairs the dropless held-expert layer computed, or None.
    """

    seq_par = policy.mesh is not None and policy.seq_axis is not None
    held_path = cfg.moe is not None and cfg.moe.capacity_factor is None

    def block(carry, layer):
        x, aux = carry
        s = x.shape[1]
        positions = jnp.arange(s)
        # sequence parallelism (Korthikanti et al.): the residual stream
        # and both norms live S-sharded; ONE forced all-gather at each
        # matmul-block entry, reduce-scatter back at the residual add.
        # Pinning the gather here stops GSPMD from resharding every
        # internal slice of the causal decomposition (measured 34x
        # collective blow-up without the pin — EXPERIMENTS.md §Perf).
        xn = common.rmsnorm(layer["ln1"], x, cfg.norm_eps)
        if seq_par:
            xn = shard_hint(xn, policy, "batch", None, None, force=True)
        if cfg.mla is not None:
            h = mla_attention_block(layer["attn"], xn, cfg, positions)
        else:
            h = attention_block(layer["attn"], xn, cfg, policy, positions,
                                window)
        x = x + h
        x = shard_hint(x, policy, "batch", "seq", None)
        hn = common.rmsnorm(layer["ln2"], x, cfg.norm_eps)
        if seq_par:
            hn = shard_hint(hn, policy, "batch", None, None, force=True)
        dt = jnp.dtype(cfg.dtype)
        held = None
        mask = (jnp.arange(s) < n_real) if n_real is not None else None
        if cfg.moe is not None and not dense and held_path:
            f, aux_l, held = held_moe_ffn(layer["moe"], hn.astype(dt),
                                          cfg.moe, mask=mask)
            aux = aux + aux_l
        elif cfg.moe is not None and not dense:
            f, aux_l = moe_ffn(layer["moe"], hn.astype(dt), cfg.moe, policy,
                               mask=mask)
            aux = aux + aux_l
        elif dense:
            ffn = jax.tree.map(lambda w: w.astype(dt), layer["ffn"])
            f = common.swiglu(ffn, hn.astype(dt))
        else:
            f = common.swiglu(layer["ffn"], hn.astype(dt))
        x = x + f.astype(x.dtype)
        x = shard_hint(x, policy, "batch", "seq", None)
        return (x, aux), held

    return block


def decoder_forward(params: dict, embeds: jnp.ndarray, cfg: ModelConfig,
                    policy: ShardingPolicy, window: Optional[int],
                    n_real: Optional[int] = None):
    """Run the layer stack (the leading dense layers, then the repeated
    ones) over input embeddings. Returns (x, aux, held pairs or None)."""
    carry = (shard_hint(embeds, policy, "batch", "seq", None),
             jnp.zeros((), jnp.float32))
    held = None
    for name, dense in (("dense_layers", True), ("layers", False)):
        if name not in params:
            continue
        block = make_block_fn(cfg, policy, window, n_real=n_real,
                              dense=dense)
        if cfg.remat:
            block = jax.checkpoint(block)
        carry, ys = jax.lax.scan(block, carry, params[name])
        if ys is not None:
            held = jnp.sum(ys)
    x, aux = carry
    return common.rmsnorm(params["ln_f"], x, cfg.norm_eps), aux, held


def logits_fn(params: dict, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    if cfg.tie_embeddings:
        return common.unembed(params["embed"], x)
    return common.unembed_untied(params["lm_head"], x)


# decode slots appended to a prefill cache (ring wraps beyond this)
PREFILL_CACHE_MARGIN = 64


def _pad_len(n: int) -> int:
    """Pad the sequence so the exact-FLOP causal halving recurses deeply:
    multiples of 256 keep several even halvings above the 512 leaf."""
    if n >= 256:
        return ((n + 255) // 256) * 256
    return n + (n % 2)  # tiny smoke shapes: just make it even


def embed_inputs(params: dict, batch: dict, cfg: ModelConfig):
    """Token (+ optional frontend) embedding. Returns (embeds, n_prefix,
    n_pad) where positions [n_prefix, n_prefix + S_text) carry the text."""
    tok_emb = common.embed(params["embed"], batch["tokens"])
    if cfg.family == "vlm":
        front = batch["frontend"].astype(tok_emb.dtype)  # (B, P, D) stub
        x = jnp.concatenate([front, tok_emb], axis=1)
        n_prefix = front.shape[1]
    else:
        x = tok_emb
        n_prefix = 0
    total = x.shape[1]
    padded = _pad_len(total)
    n_pad = padded - total
    if n_pad:
        x = jnp.pad(x, ((0, 0), (0, n_pad), (0, 0)))
    # the residual stream runs in the compute dtype (bf16): halves the
    # activation working set and the remat checkpoint stack
    return x.astype(jnp.dtype(cfg.dtype)), n_prefix, n_pad


# --------------------------------------------------------------------------
# losses & steps
# --------------------------------------------------------------------------

def make_loss_fn(cfg: ModelConfig, policy: ShardingPolicy,
                 window: Optional[int]):
    def loss_fn(params, batch):
        x, n_prefix, n_pad = embed_inputs(params, batch, cfg)
        x, aux, held = decoder_forward(params, x, cfg, policy, window,
                                       n_real=x.shape[1] - n_pad)
        s_text = batch["tokens"].shape[1]
        x_text = jax.lax.dynamic_slice_in_dim(x, n_prefix, s_text, axis=1)
        logits = logits_fn(params, x_text, cfg)
        loss = common.softmax_xent(logits, batch["labels"], cfg.vocab_size)
        metrics = {"xent": loss}
        if cfg.moe is not None:
            # the dropless layer's sequence-level loss (DeepSeek) adds
            # every MoE layer's term; the Switch loss is averaged
            if cfg.moe.capacity_factor is not None:
                aux = aux / cfg.n_layers
            metrics["moe_aux"] = aux
            loss = loss + cfg.moe.router_aux_weight * aux
        if held is not None:
            metrics["moe_held"] = held
        return loss, metrics
    return loss_fn


# --------------------------------------------------------------------------
# decode (serve_step)
# --------------------------------------------------------------------------

def _decode_attention_block(layer_attn: dict, x: jnp.ndarray, cache: dict,
                            pos, cfg: ModelConfig,
                            policy: ShardingPolicy = UNSHARDED):
    b = x.shape[0]
    hd = cfg.resolved_head_dim
    dt = jnp.dtype(cfg.dtype)
    xc = x.astype(dt)
    q = jnp.einsum("bsd,dh->bsh", xc, layer_attn["wq"].astype(dt))
    k = jnp.einsum("bsd,dh->bsh", xc, layer_attn["wk"].astype(dt))
    v = jnp.einsum("bsd,dh->bsh", xc, layer_attn["wv"].astype(dt))
    q = q.reshape(b, 1, cfg.n_heads, hd)
    k = k.reshape(b, 1, cfg.n_kv_heads, hd)
    v = v.reshape(b, 1, cfg.n_kv_heads, hd)
    posv = jnp.full((1,), pos, jnp.int32)
    q = common.apply_rope(q, posv, cfg.rope_theta)
    k = common.apply_rope(k, posv, cfg.rope_theta)
    if cfg.n_kv_heads % max(policy.model_size, 1) != 0:
        # cache is length-sharded over the model axis (see
        # make_state_spec_rule): replicate the tiny q/k/v so attention
        # reduces over the sharded T with small psums instead of
        # re-gathering the cache (flash-decode schedule)
        q = shard_hint(q, policy, "batch", None, None, None, force=True)
        k = shard_hint(k, policy, "batch", None, None, None, force=True)
        v = shard_hint(v, policy, "batch", None, None, None, force=True)
    cache = attn_lib.cache_update(cache, k, v, pos)
    o = attn_lib.decode_attention(q, cache, pos)
    o = o.reshape(b, 1, cfg.n_heads * hd)
    out = jnp.einsum("bsh,hd->bsd", o, layer_attn["wo"].astype(dt))
    return out.astype(x.dtype), cache


def make_decode_fn(cfg: ModelConfig, policy: ShardingPolicy):
    """serve_step: one token through the stack with per-layer KV caches.

    state = {"cache": stacked per-layer cache (L leading dim), "pos": ()}
    batch = {"token": (B, 1) int32}
    """

    def decode_fn(params, state, batch):
        x = common.embed(params["embed"], batch["token"]).astype(
            jnp.dtype(cfg.dtype))  # (B,1,D)
        # state["pos"] = index of the LAST written token; the incoming
        # token lives at pos+1 (ring-indexed by the cache update)
        pos = state["pos"] + 1

        def body(x, xs):
            layer, cache = xs
            h, cache = _decode_attention_block(
                layer["attn"], common.rmsnorm(layer["ln1"], x, cfg.norm_eps),
                cache, pos, cfg, policy)
            x = x + h
            hn = common.rmsnorm(layer["ln2"], x, cfg.norm_eps)
            if cfg.moe is not None:
                f, _ = moe_ffn(layer["moe"], hn.astype(jnp.dtype(cfg.dtype)),
                               cfg.moe, policy)
            else:
                f = common.swiglu(layer["ffn"], hn.astype(jnp.dtype(cfg.dtype)))
            x = x + f.astype(x.dtype)
            return x, cache

        x, new_cache = jax.lax.scan(body, x, (params["layers"], state["cache"]))
        x = common.rmsnorm(params["ln_f"], x, cfg.norm_eps)
        logits = logits_fn(params, x, cfg)
        return logits, {"cache": new_cache, "pos": pos}

    return decode_fn


def make_init_decode_state(cfg: ModelConfig):
    def init_state(batch_size: int, cache_len: int):
        hd = cfg.resolved_head_dim
        one = attn_lib.init_cache(batch_size, cache_len, cfg.n_kv_heads, hd,
                                  jnp.dtype(cfg.dtype))
        cache = jax.tree.map(
            lambda x: jnp.zeros((cfg.n_layers,) + x.shape, x.dtype), one)
        return {"cache": cache, "pos": jnp.asarray(cache_len - 1, jnp.int32)}
    return init_state


# --------------------------------------------------------------------------
# prefill
# --------------------------------------------------------------------------

def make_prefill_fn(cfg: ModelConfig, policy: ShardingPolicy,
                    window: Optional[int]):
    """Full-sequence forward that also materializes the KV cache."""

    def prefill_fn(params, batch):
        x, n_prefix, n_pad = embed_inputs(params, batch, cfg)
        s = x.shape[1]
        positions = jnp.arange(s)
        hd = cfg.resolved_head_dim
        dt = jnp.dtype(cfg.dtype)

        seq_par = policy.mesh is not None and policy.seq_axis is not None

        def body(carry, layer):
            x, aux = carry
            b = x.shape[0]
            xn = common.rmsnorm(layer["ln1"], x, cfg.norm_eps).astype(dt)
            if seq_par:  # seq-par: one pinned gather at the matmul entry
                xn = shard_hint(xn, policy, "batch", None, None, force=True)
            q = jnp.einsum("bsd,dh->bsh", xn, layer["attn"]["wq"].astype(dt))
            k = jnp.einsum("bsd,dh->bsh", xn, layer["attn"]["wk"].astype(dt))
            v = jnp.einsum("bsd,dh->bsh", xn, layer["attn"]["wv"].astype(dt))
            q = q.reshape(b, s, cfg.n_heads, hd)
            k = k.reshape(b, s, cfg.n_kv_heads, hd)
            v = v.reshape(b, s, cfg.n_kv_heads, hd)
            q = common.apply_rope(q, positions, cfg.rope_theta)
            k = common.apply_rope(k, positions, cfg.rope_theta)
            if window is not None and window < s:
                o = attn_lib.windowed_attention(q, k, v, window=window)
            else:
                o = attn_lib.causal_attention(q, k, v)
            o = o.reshape(b, s, cfg.n_heads * hd)
            h = jnp.einsum("bsh,hd->bsd", o,
                           layer["attn"]["wo"].astype(dt)).astype(x.dtype)
            x = x + h
            x = shard_hint(x, policy, "batch", "seq", None)
            hn = common.rmsnorm(layer["ln2"], x, cfg.norm_eps)
            if seq_par:
                hn = shard_hint(hn, policy, "batch", None, None, force=True)
            if cfg.moe is not None:
                mask = jnp.arange(s) < (s - n_pad)
                f, aux_l = moe_ffn(layer["moe"], hn.astype(dt), cfg.moe,
                                   policy, mask=mask)
                aux = aux + aux_l
            else:
                f = common.swiglu(layer["ffn"], hn.astype(dt))
            x = x + f.astype(x.dtype)
            x = shard_hint(x, policy, "batch", "seq", None)
            return (x, aux), {"k": k, "v": v}

        (x, _), caches = jax.lax.scan(
            body, (x, jnp.zeros((), jnp.float32)), params["layers"])
        x = common.rmsnorm(params["ln_f"], x, cfg.norm_eps)
        last = x[:, s - n_pad - 1: s - n_pad, :]
        logits = logits_fn(params, last, cfg)
        # cache headroom: decode writes at pos+1 — without slack the ring
        # would wrap and evict position 0 on the first decoded token
        caches = jax.tree.map(
            lambda c: jnp.pad(c, ((0, 0), (0, 0), (0, PREFILL_CACHE_MARGIN),
                                  (0, 0), (0, 0))), caches)
        state = {"cache": caches, "pos": jnp.asarray(s - n_pad - 1, jnp.int32)}
        return logits, state

    return prefill_fn


# --------------------------------------------------------------------------
# sharding rules
# --------------------------------------------------------------------------

def make_spec_rule(cfg: ModelConfig, policy: ShardingPolicy):
    m_ok_q = cfg.n_heads % max(policy.model_size, 1) == 0
    m_ok_kv = cfg.n_kv_heads % max(policy.model_size, 1) == 0
    m = policy.model_axis
    f = policy.fsdp_axes
    f = f[0] if f and len(f) == 1 else f

    def rule(path: str, shape) -> P:
        if policy.mesh is None:
            return P()
        stacked = path.startswith(("layers/", "dense_layers/", "triples/",
                                   "tail/"))
        lead = (None,) if stacked else ()
        if cfg.moe is not None:
            ms = moe_spec(path, shape, policy, stacked=stacked)
            if ms is not None:
                return ms
        if path.endswith("embed/table"):
            return P(m, f)
        if path.endswith("lm_head/proj"):
            return P(f, m)
        if path.endswith("attn/wq"):
            return P(*lead, f, m if m_ok_q else None)
        if path.endswith(("attn/wk", "attn/wv")):
            return P(*lead, f, m if m_ok_kv else None)
        if path.endswith("attn/wo"):
            return P(*lead, m if m_ok_q else None, f)
        if path.endswith(("ffn/w_gate", "ffn/w_up")):
            return P(*lead, f, m)
        if path.endswith("ffn/w_down"):
            return P(*lead, m, f)
        # norms and anything small: replicated
        return P(*([None] * len(shape)))

    return rule


def make_state_spec_rule(cfg: ModelConfig, policy: ShardingPolicy):
    m_ok_kv = cfg.n_kv_heads % max(policy.model_size, 1) == 0
    m_ok_hd = cfg.resolved_head_dim % max(policy.model_size, 1) == 0
    m = policy.model_axis

    def rule(path: str, shape) -> P:
        if policy.mesh is None:
            return P()
        if path.endswith(("/k", "/v")) and len(shape) == 5:
            # (L, B, T, Hkv, hd): batch over data axes; the model axis goes
            # on heads when divisible, else on the cache LENGTH — decode
            # attention then reduces over the sharded T with tiny psums
            # (flash-decode style) instead of re-gathering the cache every
            # layer (measured 47 GB/token for qwen3 when hd was sharded —
            # EXPERIMENTS.md §Perf). The cache is the dominant serve-time
            # allocation and MUST shard one way or another.
            batch = policy.dim("batch", shape[1])
            if m_ok_kv:
                return P(None, batch, None, m, None)
            if m is not None and shape[2] % max(policy.model_size, 1) == 0:
                return P(None, batch, m, None, None)
            if m_ok_hd:
                return P(None, batch, None, None, m)
            return P(None, batch, None, None, None)
        return P(*([None] * len(shape)))

    return rule


# --------------------------------------------------------------------------
# builder
# --------------------------------------------------------------------------

def build_decoder_model(cfg: ModelConfig, policy: ShardingPolicy = UNSHARDED,
                        window: Optional[int] = None) -> Model:
    window = window if window is not None else cfg.sliding_window
    # the dropless held-expert layer: a grouped matmul, built for training
    held = cfg.moe is not None and cfg.moe.capacity_factor is None
    serves = cfg.mla is None and not cfg.first_k_dense and not held
    return Model(
        config=cfg,
        policy=policy,
        init=lambda rng: init_decoder_params(rng, cfg),
        loss_fn=make_loss_fn(cfg, policy, window),
        prefill_fn=make_prefill_fn(cfg, policy, window) if serves else None,
        decode_fn=make_decode_fn(cfg, policy) if serves else None,
        init_decode_state=make_init_decode_state(cfg) if serves else None,
        spec_rule=make_spec_rule(cfg, policy),
        state_spec_rule=make_state_spec_rule(cfg, policy),
        batches_over_clients=not held,
    )
