"""Plain reference of ``deepseek-v2-lite-ep8``: one chip's share of
DeepSeek-V2-Lite (arXiv:2405.04434 §2.1-2.2; the HF ``config.json``
named in the configuration file) trained in emulated federated rounds.

Model, one sequence of S tokens (x: S x D, float32 unless the control):

* embedding lookup; then ``first_k_dense_replace`` dense layers and the
  MoE layers, each ``x += attn(rms(x)); x += ffn(rms(x))`` with RMSNorm
  ``x / sqrt(mean(x^2) + eps) * scale``; final RMSNorm; untied head over
  the vocabulary slice; mean cross-entropy over the S positions.
* MLA, no query LoRA: ``q = x W_q`` per head split into 128 no-position
  and 64 rotary dims; ``[c_kv; k_r] = x W_kva`` (512 + 64);
  ``[k_n; v] = rms(c_kv) W_kvb`` per head (128 + 128); YaRN RoPE on the
  rotary dims of q and on the one rotary key shared by every head;
  scores ``[q_n; q_r] . [k_n; k_r]`` times ``192^-1/2 * mscale^2``
  (mscale = 0.1 * mscale_all_dim * ln(factor) + 1), causal softmax,
  output ``v`` (128 per head) through ``W_o``. Computed in blocks of
  ``query_block`` queries so that the scores fit on the chip.
* YaRN inverse frequencies: ``f_i = theta^(-2i/d)``, blended as
  ``f_i / factor * r_i + f_i * (1 - r_i)`` with ``r_i`` a ramp from 0
  to 1 between the dims ``floor(c(beta_fast))`` and ``ceil(c(beta_slow))``,
  ``c(n) = d ln(L / (2 pi n)) / (2 ln theta)``, L the original length;
  cos and sin scaled by mscale(mscale) / mscale(mscale_all_dim) (= 1).
* Dense FFN and shared experts: ``(silu(x W_g) * (x W_u)) W_d``, the two
  shared experts as one of width 2 x 1,408.
* Routed experts: router logits over all ``router_experts`` (64),
  softmax, greedy top-6, weights not renormalized, times
  ``routed_scaling_factor``. Each held expert (the configuration's
  ``n_routed_experts``, experts ``expert_shard * n`` onwards) is applied
  densely to every token and weighted by its gate, zero where the token
  was not routed to it; the absent experts' part is left out.
* Balance loss (``seq_aux``): per sequence ``sum_e f_e P_e`` with
  ``f_e`` = picks of e / (S k / E) and ``P_e`` its mean probability,
  summed over the MoE layers, times ``aux_loss_alpha``, added to the
  loss.

Round: every silo starts from the global weights and takes
``local_steps`` SGD steps of rate ``local_lr`` on its own token
sequences; FedAvg over the tree equals one weighted sum over the silos
(weights 1/8); the evaluation is the loss of the global weights on one
fixed sequence; the delay is eq. 6/7 as for ``paper-mlp-1m8``.

Weights, in the layout the program keeps them: ``embed.table`` (V, D),
``dense_layers`` and ``layers`` stacked over their layers, each with
``ln1``/``ln2`` ``scale``, ``attn`` {``wq``, ``wkv_a``, ``kv_norm.scale``,
``wkv_b``, ``wo``} and either ``ffn`` {``w_gate``, ``w_up``, ``w_down``}
or ``moe`` {``router`` (D, 64), ``w_gate``/``w_up`` (n, D, F),
``w_down`` (n, F, D), ``shared`` {...}}, ``ln_f.scale``,
``lm_head.proj`` (D, V).

Departures from the HF modelling code, none of which changes the model
class: (1) the rotary dims pair dim i with dim i + 32; HF first
de-interleaves them (pairs 2i, 2i + 1), which is the same model with the
rotary rows of ``W_q`` and ``W_kva`` permuted; (2) the balance loss adds
to the loss value, where HF adds only its gradient; (3) no attention
dropout, no KV cache, no query LoRA (V2-Lite has none).

Arithmetic is in ``dtype``: float32 at the ``highest`` matmul precision
is the reference; bfloat16 (weights, updates and activations) is the
control.
"""
from __future__ import annotations

import math

import numpy as np

from harness import tree


def yarn_frequencies(dim: int, theta: float, rs: dict) -> np.ndarray:
    def corr(n):
        return (dim * math.log(rs["original_max_position_embeddings"]
                               / (n * 2 * math.pi))) / (2 * math.log(theta))
    low = max(math.floor(corr(rs["beta_fast"])), 0)
    high = min(math.ceil(corr(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    base = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    return (base / rs["factor"] * ramp + base * (1.0 - ramp)).astype(
        np.float32)


def _mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def make_loss(cfg: dict, dtype="float32", query_block: int = 512,
              drop_expert=None):
    """(params, tokens (S,), labels (S,)) -> loss, for one sequence."""
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(dtype)
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, r = cfg["v_head_dim"], cfg["kv_lora_rank"]
    eps = cfg["rms_norm_eps"]
    rs = cfg["rope_scaling"]
    inv = yarn_frequencies(dr, cfg["rope_theta"], rs)
    msc = _mscale(rs["factor"], rs["mscale"]) / \
        _mscale(rs["factor"], rs["mscale_all_dim"])
    scale = (dn + dr) ** -0.5 * _mscale(rs["factor"],
                                        rs["mscale_all_dim"]) ** 2
    E, k = cfg["router_experts"], cfg["num_experts_per_tok"]
    n_held = cfg["n_routed_experts"]
    lo = cfg["expert_shard"] * n_held
    V = cfg["vocab_size"]

    def rms(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                 + dt.type(eps)) * w

    def rope(x, pos):                       # x (S, h, dr)
        ang = pos[:, None].astype(jnp.float32) * inv[None, :]
        cos = (jnp.cos(ang) * msc)[:, None, :].astype(dt)
        sin = (jnp.sin(ang) * msc)[:, None, :].astype(dt)
        x1, x2 = x[..., :dr // 2], x[..., dr // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)

    def attention(p, x):
        S = x.shape[0]
        pos = jnp.arange(S)
        q = (x @ p["wq"]).reshape(S, H, dn + dr)
        kva = x @ p["wkv_a"]
        kv = (rms(kva[:, :r], p["kv_norm"]["scale"]) @ p["wkv_b"]).reshape(
            S, H, dn + dv)
        q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], pos)], -1)
        k_r = rope(kva[:, None, r:], pos)
        key = jnp.concatenate([kv[..., :dn],
                               jnp.broadcast_to(k_r, (S, H, dr))], -1)
        v = kv[..., dn:]
        B = min(query_block, S)

        @jax.checkpoint
        def block(i):
            qb = jax.lax.dynamic_slice_in_dim(q, i * B, B, 0)
            s = jnp.einsum("qhd,khd->hqk", qb, key) * dt.type(scale)
            seen = pos[None, :] <= (i * B + jnp.arange(B))[:, None]
            s = jnp.where(seen[None], s, -jnp.inf)
            return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)
        o = jax.lax.map(block, jnp.arange(S // B)).reshape(S, H * dv)
        return o @ p["wo"]

    def ffn(p, x):
        return (jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]

    def moe(p, x):
        S = x.shape[0]
        logits = x.astype(jnp.float32) @ p["router"].astype(jnp.float32)
        probs = jax.nn.softmax(logits, -1)
        top_w, top_i = jax.lax.top_k(probs, k)
        top_w = top_w * cfg["routed_scaling_factor"]
        gates = jnp.zeros((S, E), jnp.float32).at[
            jnp.arange(S)[:, None], top_i].set(top_w)[:, lo:lo + n_held]
        if drop_expert is not None:
            gates = gates.at[:, drop_expert].set(0.0)

        def expert(y, e):       # held expert e, densely, weighted by its gate
            pe = {n: p[n][e] for n in ("w_gate", "w_up", "w_down")}
            g = jax.lax.dynamic_slice_in_dim(gates, e, 1, 1).astype(dt)
            return y + g * ffn(pe, x), None
        y = jax.lax.scan(expert, ffn(p["shared"], x), jnp.arange(n_held))[0]
        picks = jnp.zeros(E, jnp.float32).at[top_i.reshape(-1)].add(1.0)
        aux = jnp.sum(picks / (S * k / E) * probs.mean(0))
        return y, aux

    def layers(dense):
        @jax.checkpoint
        def body(carry, p):
            x, aux = carry
            x = x + attention(p["attn"], rms(x, p["ln1"]["scale"]))
            h = rms(x, p["ln2"]["scale"])
            if dense:
                return (x + ffn(p["ffn"], h), aux), None
            y, a = moe(p["moe"], h)
            return (x + y, aux + a), None
        return body

    def loss(params, tokens, labels):
        carry = (params["embed"]["table"][tokens], jnp.zeros((), jnp.float32))
        carry = jax.lax.scan(layers(True), carry, params["dense_layers"])[0]
        x, aux = jax.lax.scan(layers(False), carry, params["layers"])[0]
        x = rms(x, params["ln_f"]["scale"])
        logits = (x @ params["lm_head"]["proj"]).astype(jnp.float32)
        gold = jnp.take_along_axis(logits[:, :V], labels[:, None], -1)[:, 0]
        xent = jnp.mean(jax.nn.logsumexp(logits[:, :V], -1) - gold)
        return xent + cfg["aux_loss_alpha"] * aux

    return loss


def leaf_norms(a: list, b: list) -> dict:
    """Float64 norms of b - a (lists of host arrays, the weights' leaves
    in order), in two groups: ``"leaf"``, one per leaf, and ``"expert"``,
    one per held expert of each MoE layer and each of its three
    matrices (the leaves stacked as (MoE layers, held experts, in, out),
    the only 4-D ones), so that one expert's part is not lost in the
    norm of eight."""
    leaf, expert = [], []
    for x, y in zip(a, b, strict=True):
        d = np.asarray(y, np.float64) - np.asarray(x, np.float64)
        if d.ndim == 4:
            expert.extend(np.linalg.norm(
                d.reshape(d.shape[0] * d.shape[1], -1), axis=1))
        else:
            leaf.append(np.linalg.norm(d.ravel()))
    return {"leaf": np.asarray(leaf), "expert": np.asarray(expert)}


def train_rounds(cfg: dict, traffic: dict, init, data, rounds: int,
                 dtype="float32", clients=None, first_round: int = 0,
                 drop_expert=None) -> dict:
    """Run ``rounds`` federated rounds, the first of them round
    ``first_round``, from ``init`` (the weights as a pytree of host
    arrays). ``data.client_batch(c, 1, step)`` and ``data.eval_batch()``
    give ``{"tokens", "labels"}`` of shape (1, S).

    Returns ``{"loss": [per round], "norms": [per round, ``leaf_norms``
    of (init, weights after the round)], "final": last weights
    (host arrays)}``. ``clients`` merges only the first that many silos,
    renormalizing their weights; ``drop_expert`` leaves one held
    expert's output out (faults to read)."""
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(dtype)
    steps, lr = traffic["local_steps"], cfg["local_lr"]
    n = traffic["clients"]
    merged = n if clients is None else clients
    weights = np.where(np.arange(n) < merged, 1.0 / merged, 0.0)
    loss = make_loss(cfg, dtype, drop_expert=drop_expert)

    @jax.jit
    def step(p, tokens, labels):
        g = jax.grad(loss)(p, tokens, labels)
        return jax.tree.map(lambda a, b: a - (lr * b).astype(dt), p, g)

    @jax.jit
    def fold(acc, p, w):
        return jax.tree.map(lambda a, b: a + w.astype(dt) * b, acc, p)

    evaluate = jax.jit(loss)
    ev = data.eval_batch()
    init_leaves = jax.tree.leaves(init)
    params = jax.tree.map(lambda a: jnp.asarray(a, dt), init)
    out = {"loss": [], "norms": []}
    for r in range(first_round, first_round + rounds):
        acc = jax.tree.map(jnp.zeros_like, params)
        for c in range(merged):
            p = params
            for t in range(steps):
                b = data.client_batch(c, 1, r * steps + t)
                p = step(p, jnp.asarray(b["tokens"][0]),
                         jnp.asarray(b["labels"][0]))
            acc = fold(acc, p, jnp.float32(weights[c]))
            del p
        params = acc
        out["loss"].append(float(evaluate(params, jnp.asarray(ev["tokens"][0]),
                                          jnp.asarray(ev["labels"][0]))))
        host = [np.asarray(x, np.float32) for x in jax.tree.leaves(params)]
        out["norms"].append(leaf_norms(init_leaves, host))
    out["final"] = jax.tree.unflatten(jax.tree.structure(params), host)
    return out


def round_tpd(placements: np.ndarray, pool: dict, traffic: dict, cfg: dict,
              dtype=np.float64) -> np.ndarray:
    """(R, D) placements -> (R,) round delays (deterministic emulation):
    every silo's training costs ``local_steps / pspeed``, the round waits
    for the slowest; a cluster costs ``sum(mdatasize of its members) /
    payload_scale / pspeed_host + comm_latency * members``; levels add
    their slowest cluster; the sum is scaled by ``time_scale``."""
    placements = np.asarray(placements, np.int64)
    depth, width = traffic["depth"], traffic["width"]
    pspeed = np.asarray(pool["pspeed"]).astype(dtype)
    train = np.max(dtype(traffic["local_steps"]) / pspeed)
    load, members = tree.slot_loads(placements, pool["mdatasize"], depth,
                                    width, dtype)
    delay = load / dtype(cfg["payload_scale"]) / pspeed[placements] + \
        dtype(traffic["comm_latency"]) * members.astype(dtype)
    agg = tree.sum_of_level_max(delay, depth, width)
    return (train + agg) * dtype(cfg["time_scale"])
