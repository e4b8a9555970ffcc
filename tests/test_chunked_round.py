"""The client-chunked round against the vmapped round: one engine, whose
chunk is the number of clients trained at once. The chunk is steered by
replacing the orchestrator's ``_client_chunk`` in the test; the program
has no option for it."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.hierarchy import ClientPool, Hierarchy
from repro.data.synthetic import make_federated_dataset
from repro.fl.orchestrator import FederatedOrchestrator
from repro.models import get_model
from repro.utils import tracing


def _orch(cfg, h, data, chunk, monkeypatch, **kw):
    orch = FederatedOrchestrator(
        get_model(cfg), h, ClientPool.random(h.total_clients, seed=1), data,
        local_steps=2, batch_size=kw.pop("batch_size", 16), seed=0, **kw)
    if chunk is not None:
        monkeypatch.setattr(orch, "_client_chunk", lambda: chunk)
    return orch


def _rounds(orch, placements):
    orch.warmup()
    recs = [orch.run_round(r, p) for r, p in enumerate(placements)]
    return recs, jax.tree.map(np.asarray, orch.params)


def _placements(h, n, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.permutation(h.total_clients)[:h.dimensions] for _ in range(n)]


def _assert_close(recs_a, params_a, recs_b, params_b, rtol, atol):
    for a, b in zip(recs_a, recs_b, strict=True):
        assert a.tpd == b.tpd                   # eq. 6 charge: exact
        assert a.train_time == b.train_time
        assert a.agg_time == b.agg_time
        np.testing.assert_allclose(a.loss, b.loss, rtol=rtol)
    for x, y in zip(jax.tree.leaves(params_a), jax.tree.leaves(params_b),
                    strict=True):
        np.testing.assert_allclose(x, y, rtol=rtol, atol=atol)


@pytest.mark.parametrize("chunk", [1, 2, 11])
def test_chunked_mlp_round_matches_vmapped(chunk, monkeypatch):
    """The paper MLP, 11 clients on a depth-2 width-2 tree, non-IID
    shards of several sizes (so the round has several input buckets).
    Tolerance: the fold adds the same weighted updates in the same
    [host, children...] order as the segment sums, so only XLA's fusion
    of multiply and add (one rounding less) separates the two: float32
    round-off of weights of order 0.1, three rounds."""
    cfg = get_config("paper-mlp-1m8")
    h = Hierarchy(depth=2, width=2, trainers_per_leaf=2, n_clients=11)
    data = make_federated_dataset(cfg, h.total_clients, seed=0)
    ps = _placements(h, 3)
    ref = _rounds(_orch(cfg, h, data, None, monkeypatch), ps)
    got = _rounds(_orch(cfg, h, data, chunk, monkeypatch), ps)
    _assert_close(*ref, *got, rtol=1e-5, atol=1e-6)


def _dsv2_reduced():
    cfg = get_config("deepseek-v2-lite").reduced()
    return cfg.replace(moe=dataclasses.replace(cfg.moe, n_experts=8, top_k=3,
                                               n_held=4, expert_shard=1))


@pytest.mark.parametrize("chunk", [1, 2, 8])
def test_chunked_dsv2_round_matches_vmapped(chunk, monkeypatch):
    """The reduced DeepSeek-V2-Lite (MLA, a dense layer, held experts) on
    8 silos, depth 2 width 2. Tolerance: as for the MLP, the same sums in
    the same order; the bfloat16 activations are computed identically on
    both paths, so float32 round-off of the folds is all that differs."""
    cfg = _dsv2_reduced()
    h = Hierarchy(depth=2, width=2, trainers_per_leaf=2, n_clients=8)
    data = make_federated_dataset(cfg, h.total_clients, seed=0, seq_len=32)
    ps = _placements(h, 2, seed=3)
    ref = _rounds(_orch(cfg, h, data, None, monkeypatch, batch_size=1), ps)
    got = _rounds(_orch(cfg, h, data, chunk, monkeypatch, batch_size=1), ps)
    _assert_close(*ref, *got, rtol=2e-5, atol=1e-6)


def test_client_chunk_from_memory(monkeypatch):
    """The chunk comes from the device's memory limit and the model's
    bytes: no limit (the CPU) trains every client at once; a limit that
    holds the global model, one accumulator per level and three buffers
    per client trains that many."""
    cfg = get_config("paper-mlp-1m8")
    h = Hierarchy(depth=2, width=2, trainers_per_leaf=2, n_clients=11)
    data = make_federated_dataset(cfg, h.total_clients, seed=0)
    orch = _orch(cfg, h, data, None, monkeypatch)
    assert orch._client_chunk() == 11
    model = sum(x.nbytes for x in jax.tree.leaves(orch.params))

    class Dev:
        def __init__(self, limit):
            self.limit = limit

        def memory_stats(self):
            return {"bytes_limit": self.limit}
    for fits, want in ((1, 1), (2, 2), (0, 1), (40, 11)):
        limit = (3 + 3 * fits) * model / 0.75
        monkeypatch.setattr(jax, "devices", lambda limit=limit: [Dev(limit)])
        assert orch._client_chunk() == want


def test_chunked_round_counts(monkeypatch):
    """Traced, the chunked round counts its chunks, its ``local_all``
    calls and the held experts' token-expert pairs; the visit order
    folds every client exactly once."""
    cfg = _dsv2_reduced()
    h = Hierarchy(depth=2, width=2, trainers_per_leaf=2, n_clients=8)
    data = make_federated_dataset(cfg, h.total_clients, seed=0, seq_len=32)
    orch = _orch(cfg, h, data, 3, monkeypatch, batch_size=1)
    placement = np.arange(h.dimensions)
    steps = orch._visit_order(placement)
    clients = [c for kind, c, _ in steps if kind == "client"]
    assert sorted(clients) == list(range(8))
    assert [s[1:] for s in steps if s[0] == "merge"] == [(1, 0), (2, 0)]
    orch.warmup()
    tracing.reset()
    monkeypatch.setattr(tracing, "recording", lambda: True)
    monkeypatch.setattr(tracing.TRACER, "count",
                        lambda name, n=1: tracing.TRACER._counters.__setitem__(
                            name, tracing.TRACER._counters[name] + n))
    monkeypatch.setattr(tracing, "count", tracing.TRACER.count)
    orch.run_round(0, placement)
    counters = tracing.snapshot()["counters"]
    assert counters["round.client_chunks"] == 3          # 3 + 3 + 2
    assert counters["round.local_calls"] == 3
    # every token of 8 clients x 2 steps x 32 positions picks 3 of 8
    # experts; 4 are held here, so between none and all of the picks
    pairs = counters["moe.held_assignments"]
    assert 0 < pairs <= 8 * 2 * 32 * 3 * (cfg.n_layers - 1)
    assert counters["moe.eval_held_assignments"] > 0
    tracing.reset()
