"""DeepSeek-V2-Lite in the program against plain references, on the CPU
at ``reduced()`` size with seeded random weights: latent attention, the
held-expert layer and its share identity, the grouped no-drop expert
path, the whole model's loss and gradients, and one federated round
against the benchmark's reference (``bench/configs/
deepseek-v2-lite-ep8.py``). The published configuration is checked
against the catalog's numbers."""
import dataclasses
import importlib.util
import json
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.hierarchy import ClientPool, Hierarchy
from repro.fl.orchestrator import FederatedOrchestrator
from repro.models import common, get_model
from repro.models.moe import held_experts, held_moe_ffn, route_topk
from repro.models.transformer import mla_attention_block

BENCH = Path(__file__).resolve().parents[1] / "bench"


def _load(path: Path):
    if str(BENCH) not in sys.path:
        sys.path.insert(0, str(BENCH))
    spec = importlib.util.spec_from_file_location(
        "test_" + path.stem.replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _load(BENCH / "configs" / "deepseek-v2-lite-ep8.py")


@pytest.fixture(scope="module")
def lm_cell():
    return _load(BENCH / "drivers" / "emulated_lm_round.py")


def _cfg(n_experts=8, top_k=3, n_held=None, shard=0, dtype="float32"):
    c = get_config("deepseek-v2-lite").reduced()
    return c.replace(dtype=dtype, moe=dataclasses.replace(
        c.moe, n_experts=n_experts, top_k=top_k, n_held=n_held,
        expert_shard=shard))


def _ref_cfg(lm_cell, m):
    file = json.loads((BENCH / "configs" /
                       "deepseek-v2-lite-ep8.json").read_text())
    return dict(file, **{k: f(m) for k, f in lm_cell.WIDTHS.items()})


# ---------------------------------------------------------------------------
# the published configuration
# ---------------------------------------------------------------------------

CATALOG = {"num_hidden_layers": 27, "hidden_size": 2048,
           "intermediate_size": 10944, "moe_intermediate_size": 1408,
           "n_routed_experts": 64, "num_experts_per_tok": 6,
           "n_shared_experts": 2, "num_attention_heads": 16,
           "num_key_value_heads": 16, "kv_lora_rank": 512,
           "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
           "v_head_dim": 128, "vocab_size": 102400, "rms_norm_eps": 1e-6,
           "rope_theta": 10000, "first_k_dense_replace": 1,
           "norm_topk_prob": False, "routed_scaling_factor": 1,
           "tie_word_embeddings": False}


def test_published_config_matches_catalog(lm_cell):
    """The program's config is the published model, and the benchmark's
    file changes only the keys it lists in ``reduced``."""
    m = get_config("deepseek-v2-lite")
    got = {k: f(m) for k, f in lm_cell.WIDTHS.items()}
    got["n_routed_experts"] = m.moe.n_experts
    assert {k: got[k] for k in CATALOG} == CATALOG
    y = m.rope_scaling
    assert (y.factor, y.original_max_position, y.beta_fast, y.beta_slow,
            y.mscale, y.mscale_all_dim) == (40, 4096, 32, 1, 0.707, 0.707)
    assert m.mla is not None and m.moe.capacity_factor is None
    file = json.loads((BENCH / "configs" /
                       "deepseek-v2-lite-ep8.json").read_text())
    cut = set(file["reduced"])
    assert cut == {"num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert {k: file[k] for k in CATALOG if k not in cut} == \
        {k: v for k, v in CATALOG.items() if k not in cut}
    assert {k: file["published"][k] for k in cut} == {k: CATALOG[k]
                                                       for k in cut}
    # the program's cut model is the file's, width for width
    cut_model, _ = lm_cell.program_model(file, {})
    sizes = jax.eval_shape(get_model(cut_model).init, jax.random.key(0))
    assert sum(math.prod(x.shape) for x in jax.tree.leaves(sizes)) == \
        file["n_params"] == 535_060_992
    whole = jax.eval_shape(get_model(m).init, jax.random.key(0))
    assert sum(math.prod(x.shape) for x in jax.tree.leaves(whole)) == \
        file["n_params_whole"]


def test_reduced_keeps_every_mechanism():
    r = get_config("deepseek-v2-lite").reduced()
    assert r.mla is not None and r.rope_scaling is not None
    assert r.first_k_dense == 1 and r.n_layers == 3
    assert r.moe.n_shared_experts == 2
    assert r.moe.capacity_factor is None


# ---------------------------------------------------------------------------
# latent attention
# ---------------------------------------------------------------------------

def _plain_mla(p, x, cfg):
    """Textbook MLA on one sequence with a full causal mask, float32."""
    m, h = cfg.mla, cfg.n_heads
    s = x.shape[0]
    dn, dr, dv, r = (m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim,
                     m.kv_lora_rank)
    q = (x @ p["wq"]).reshape(s, h, dn + dr)
    kva = x @ p["wkv_a"]
    c = kva[:, :r]
    c = c / np.sqrt(np.mean(c * c, -1, keepdims=True) + cfg.norm_eps) \
        * p["kv_norm"]["scale"]
    kv = (c @ p["wkv_b"]).reshape(s, h, dn + dv)
    y = cfg.rope_scaling
    inv = np.asarray(common.yarn_frequencies(dr, cfg.rope_theta, y))
    ang = np.arange(s)[:, None] * inv[None]

    def rot(t):                                  # (s, heads, dr)
        a, b = t[..., :dr // 2], t[..., dr // 2:]
        cos, sin = np.cos(ang)[:, None], np.sin(ang)[:, None]
        return np.concatenate([a * cos - b * sin, a * sin + b * cos], -1)
    qf = np.concatenate([q[..., :dn], rot(q[..., dn:])], -1)
    kr = rot(kva[:, None, r:])
    kf = np.concatenate([kv[..., :dn], np.repeat(kr, h, axis=1)], -1)
    msc = 0.1 * y.mscale_all_dim * math.log(y.factor) + 1.0
    scores = np.einsum("qhd,khd->hqk", qf, kf) * (dn + dr) ** -0.5 * msc ** 2
    scores = np.where(np.tril(np.ones((s, s), bool))[None], scores, -np.inf)
    w = np.exp(scores - scores.max(-1, keepdims=True))
    w /= w.sum(-1, keepdims=True)
    o = np.einsum("hqk,khd->qhd", w, kv[..., dn:]).reshape(s, h * dv)
    return o @ p["wo"]


def test_mla_matches_plain_mla():
    """Blockwise causal attention with qk dim != v dim, the shared rotary
    key and the YaRN scale against a textbook float64 MLA. Tolerance:
    float32 round-off of 64-position sums."""
    cfg = _cfg()
    params = get_model(cfg).init(jax.random.key(1))
    p = jax.tree.map(lambda a: a[0], params["layers"]["attn"])
    x = jax.random.normal(jax.random.key(2), (64, cfg.d_model), jnp.float32)
    got = mla_attention_block(p, x[None], cfg, jnp.arange(64))[0]
    want = _plain_mla(jax.tree.map(lambda a: np.asarray(a, np.float64), p),
                      np.asarray(x, np.float64), cfg)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-5)


def test_yarn_frequencies_blend():
    """YaRN keeps the fast dims, divides the slow ones by the factor."""
    y = get_config("deepseek-v2-lite").rope_scaling
    f = np.asarray(common.yarn_frequencies(64, 10000.0, y))
    base = 1.0 / 10000.0 ** (np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(f[:10], base[:10], rtol=1e-6)
    np.testing.assert_allclose(f[-8:], base[-8:] / 40, rtol=1e-6)
    assert np.all(np.diff(f) < 0)


# ---------------------------------------------------------------------------
# the held-expert layer
# ---------------------------------------------------------------------------

def _dense_experts(x, top_w, top_i, p, n_experts, lo=0):
    """Every held expert applied to every token, weighted by its gate
    (zero where the token was not routed to it), float64."""
    x = np.asarray(x, np.float64)
    gates = np.zeros((x.shape[0], n_experts))
    np.put_along_axis(gates, np.asarray(top_i), np.asarray(top_w), axis=1)
    out = np.zeros_like(x)
    for e in range(p["w_gate"].shape[0]):
        g = x @ np.asarray(p["w_gate"][e], np.float64)
        u = x @ np.asarray(p["w_up"][e], np.float64)
        h = g / (1 + np.exp(-g)) * u
        out += gates[:, lo + e:lo + e + 1] * (h @ np.asarray(p["w_down"][e],
                                                             np.float64))
    return out


def _moe_params(cfg, key):
    return get_model(cfg).init(key)["layers"]["moe"]


@pytest.mark.parametrize("skew", [0.0, 8.0])
def test_grouped_path_matches_dense_experts(skew):
    """The grouped no-drop path equals the dense gate-weighted experts,
    including a skewed router under which one expert takes most of the
    tokens (nothing is dropped, however many pick it). Tolerance:
    float32 round-off of sums over D = 256 and F = 64."""
    cfg = _cfg()
    p = jax.tree.map(lambda a: a[0], _moe_params(cfg, jax.random.key(3)))
    u = jnp.ones(cfg.d_model) / np.sqrt(cfg.d_model)
    p["router"] = p["router"].at[:, 5].add(skew * u)
    x = jax.random.normal(jax.random.key(4), (96, cfg.d_model),
                          jnp.float32) + 2.0 * u
    _, top_w, top_i = route_topk(x, p["router"], cfg.moe)
    if skew:      # nearly every token picks expert 5
        assert np.mean(np.any(np.asarray(top_i) == 5, axis=1)) > 0.9
    out, pairs = held_experts(x, top_w, top_i, p, cfg.moe)
    assert int(pairs) == 96 * cfg.moe.top_k
    want = _dense_experts(x, top_w, top_i, p, cfg.moe.n_experts)
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-4, atol=1e-5)


def test_held_shares_sum_to_uncut_layer():
    """Guide §4's identity: the routed parts of the four shares of two
    held experts each, plus the shared experts counted once, equal the
    uncut layer with all eight experts. Tolerance: float32 round-off."""
    full = _cfg()
    p = jax.tree.map(lambda a: a[0], _moe_params(full, jax.random.key(5)))
    x = jax.random.normal(jax.random.key(6), (2, 24, full.d_model),
                          jnp.float32)
    want, aux, pairs = held_moe_ffn(p, x, full.moe)
    assert int(pairs) == 48 * full.moe.top_k
    x2d = x.reshape(48, -1)
    _, top_w, top_i = route_topk(x2d, p["router"], full.moe)
    total = common.swiglu(p["shared"], x2d)
    counted = 0
    for shard in range(4):
        m = dataclasses.replace(full.moe, n_held=2, expert_shard=shard)
        share = {k: p[k][2 * shard:2 * shard + 2]
                 for k in ("w_gate", "w_up", "w_down")}
        part, n = held_experts(x2d, top_w, top_i, share, m)
        counted += int(n)
        # each share is its own dense reference's part, too
        np.testing.assert_allclose(
            np.asarray(part), _dense_experts(x2d, top_w, top_i, share, 8,
                                             lo=2 * shard),
            rtol=1e-4, atol=1e-5)
        total = total + part
    assert counted == int(pairs)
    np.testing.assert_allclose(np.asarray(total).reshape(want.shape),
                               np.asarray(want), rtol=1e-4, atol=1e-5)
    # the balance loss reads all eight experts' probabilities on a share
    m = dataclasses.replace(full.moe, n_held=2, expert_shard=3)
    share = {k: (p[k][6:] if k.startswith("w_") else p[k]) for k in p}
    _, aux_share, _ = held_moe_ffn(share, x, m)
    assert float(aux_share) == pytest.approx(float(aux))


def test_top_k_weights_not_renormalized():
    cfg = _cfg()
    p = jax.tree.map(lambda a: a[0], _moe_params(cfg, jax.random.key(7)))
    x = jax.random.normal(jax.random.key(8), (16, cfg.d_model), jnp.float32)
    probs, top_w, _ = route_topk(x, p["router"], cfg.moe)
    np.testing.assert_allclose(np.asarray(top_w),
                               -np.sort(-np.asarray(probs), -1)[:, :3],
                               rtol=1e-6)
    assert np.all(np.asarray(top_w).sum(-1) < 1.0)


# ---------------------------------------------------------------------------
# the whole model and one federated round against the bench reference
# ---------------------------------------------------------------------------

def _tokens(vocab, s, seed):
    rng = np.random.default_rng(seed)
    t = rng.integers(0, vocab, size=s + 1).astype(np.int32)
    return t[:-1], t[1:]


@pytest.mark.parametrize("n_held,shard", [(None, 0), (4, 1)])
def test_model_loss_and_grads_match_reference(ref, lm_cell, n_held, shard):
    """The program's loss and gradients (float32 activations) against the
    plain reference, whole model, on one chip's share of the experts too.
    Tolerance: float32 round-off through three layers and a 512-way
    softmax; the routing is the same (identical float32 router inputs)."""
    m = _cfg(n_held=n_held, shard=shard)
    model = get_model(m)
    params = model.init(jax.random.key(9))
    tokens, labels = _tokens(m.vocab_size, 64, 0)
    batch = {"tokens": jnp.asarray(tokens)[None],
             "labels": jnp.asarray(labels)[None]}
    rcfg = _ref_cfg(lm_cell, m)
    loss = ref.make_loss(rcfg, "float32", query_block=16)
    got, g_got = jax.value_and_grad(lambda p: model.loss_fn(p, batch)[0])(
        params)
    want, g_want = jax.value_and_grad(loss)(params, jnp.asarray(tokens),
                                           jnp.asarray(labels))
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for a, b in zip(jax.tree.leaves(g_got), jax.tree.leaves(g_want),
                    strict=True):
        scale = float(jnp.max(jnp.abs(b))) + 1e-12
        assert float(jnp.max(jnp.abs(a - b))) / scale < 2e-4


def test_federated_round_matches_reference(ref, lm_cell):
    """One round of the reduced model through ``FederatedOrchestrator.
    run_round`` (pso placement, the chunked engine with one silo at a
    time) against the reference's ``train_rounds``: the loss after the
    round and every leaf's update norm, each held expert's apart.
    Tolerance: float32 round-off of two SGD steps on 8 silos and their
    weighted sum."""
    m = _cfg(n_held=4, shard=1)
    traffic = {"clients": 8, "depth": 2, "width": 2, "local_steps": 2,
               "seq_len": 32, "comm_latency": 0.0}
    rcfg = dict(_ref_cfg(lm_cell, m), local_lr=0.5)
    data = lm_cell.Tokens(2**33 + 5, 8, m.vocab_size, traffic["seq_len"])
    model = get_model(m)
    h = Hierarchy(depth=2, width=2, trainers_per_leaf=2, n_clients=8)
    orch = FederatedOrchestrator(
        model, h, ClientPool.random(8, seed=0), data, local_lr=0.5,
        local_steps=2, batch_size=1, seed=0)
    orch._client_chunk = lambda: 1
    orch.set_global(lm_cell.init_weights(model, 11))
    init = jax.tree.map(np.asarray, orch.params)
    orch.warmup()
    rec = orch.run_round(0, np.asarray([3, 0, 6]))
    want = ref.train_rounds(rcfg, traffic, init, data, 1)
    assert rec.loss == pytest.approx(want["loss"][0], rel=1e-5)
    got = ref.leaf_norms(jax.tree.leaves(init),
                         [np.asarray(x) for x in jax.tree.leaves(orch.params)])
    assert got.keys() == want["norms"][0].keys() == {"leaf", "expert"}
    assert len(got["expert"]) == 3 * (m.n_layers - 1) * 4
    for g in got:
        np.testing.assert_allclose(got[g], want["norms"][0][g], rtol=2e-3,
                                   atol=1e-7)
