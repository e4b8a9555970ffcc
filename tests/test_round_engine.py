"""The round engine vs the plain per-client / per-cluster reference
(``tests/_round_reference.py``): identical training math, identical
deterministic TPD, and the eq. 6/7 composition contract against the
cost model (heterogeneous mdatasize)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _round_reference import reference_round, reference_run

from repro.configs import get_config
from repro.core.cost_model import CostModel
from repro.core.hierarchy import ClientPool, Hierarchy
from repro.core.registry import create_strategy
from repro.data.synthetic import make_federated_dataset
from repro.fl.aggregation import batched_hierarchical_fedavg, hierarchical_fedavg
from repro.fl.orchestrator import FederatedOrchestrator, FederatedRunResult
from repro.models import get_model


@pytest.fixture(scope="module")
def mlp_setup():
    cfg = get_config("paper-mlp-1m8")
    model = get_model(cfg)
    h = Hierarchy(depth=2, width=2, trainers_per_leaf=2, n_clients=11)
    clients = ClientPool.random(h.total_clients, seed=0)
    data = make_federated_dataset(cfg, h.total_clients, seed=0)
    return model, h, clients, data


def _run(mlp_setup, reference, rounds=4, **kw):
    model, h, clients, data = mlp_setup
    strat = create_strategy("pso", h, seed=0)
    orch = FederatedOrchestrator(model, h, clients, data, local_steps=2,
                                 batch_size=16, seed=0, **kw)
    if reference:
        return reference_run(orch, strat, rounds, seed=0)
    return orch.run(strat, rounds=rounds)


def test_batched_engine_matches_loop_trace(mlp_setup):
    """The round engine's contract: the reference's per-round
    loss/accuracy/TPD trace on the paper MLP config (identical training
    math; fp reassociation in the per-level segment sums is the only
    permitted delta)."""
    a = _run(mlp_setup, reference=True)
    b = _run(mlp_setup, reference=False)
    for ra, rb in zip(a.rounds, b.rounds, strict=True):
        assert ra.placement == rb.placement
        assert ra.tpd == rb.tpd                 # deterministic: exact
        assert ra.accuracy == rb.accuracy
        assert abs(ra.loss - rb.loss) < 5e-6


def test_engines_agree_with_noise_and_comm(mlp_setup):
    """rng stream parity: per-cluster noise draws must line up exactly."""
    a = _run(mlp_setup, reference=True, rounds=3, rng_noise=0.05,
             comm_latency=0.01)
    b = _run(mlp_setup, reference=False, rounds=3, rng_noise=0.05,
             comm_latency=0.01)
    np.testing.assert_array_equal(a.tpds, b.tpds)


@pytest.mark.parametrize("run", ["reference", "orchestrator", "defaults"])
def test_deterministic_tpd_composes_cost_model(run):
    """Regression for the child-payload bug (charged mdatasize[0] for
    every child): with heterogeneous mdatasize, the round's agg time
    must equal the CostModel eq. 6/7 composition (scaled by the /10
    emulation factor), for the reference and the orchestrator, and for
    an orchestrator built with every default (whose timing is the eq. 6
    charge, never the wall clock)."""
    cfg = get_config("paper-mlp-1m8")
    model = get_model(cfg)
    h = Hierarchy(depth=2, width=2, trainers_per_leaf=2, n_clients=12)
    clients = ClientPool.random(h.total_clients, seed=3)
    rng = np.random.default_rng(7)
    clients.mdatasize = rng.uniform(1.0, 40.0, h.total_clients)
    data = make_federated_dataset(cfg, h.total_clients, seed=3)
    placement = rng.permutation(h.total_clients)[: h.dimensions]
    strat = create_strategy("static", h, placement=placement)
    if run == "defaults":
        orch = FederatedOrchestrator(model, h, clients, data)
        steps = 4
    else:
        orch = FederatedOrchestrator(model, h, clients, data,
                                     local_steps=1, batch_size=8, seed=3)
        steps = 1
    if run == "reference":
        res = reference_run(orch, strat, 1, seed=3)
    else:
        res = orch.run(strat, rounds=1)
    r = res.rounds[0]
    cm = CostModel(h, clients)
    assert r.agg_time == pytest.approx(cm.tpd(placement) / 10.0, rel=1e-9)
    assert r.train_time == pytest.approx(steps / clients.pspeed.min())
    assert r.tpd == pytest.approx(r.train_time + r.agg_time)


def test_batched_fedavg_matches_sequential_reference():
    """segment-sum levels == the per-cluster sequential reference for
    random placements and weights."""
    rng = np.random.default_rng(0)
    for _ in range(5):
        depth = int(rng.integers(1, 4))
        width = int(rng.integers(1, 4)) if depth > 1 else 2
        h = Hierarchy(depth=depth, width=width, trainers_per_leaf=2)
        n = h.total_clients
        updates = [
            {"w": jnp.asarray(rng.standard_normal((3, 4)), jnp.float32),
             "b": jnp.asarray(rng.standard_normal((5,)), jnp.float32)}
            for _ in range(n)]
        w = rng.dirichlet(np.ones(n)).astype(np.float32)
        placement = rng.permutation(n)[: h.dimensions]
        ref = hierarchical_fedavg(updates, list(w), h, placement)
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *updates)
        got = batched_hierarchical_fedavg(stacked, w, h, placement)
        for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(got),
                        strict=True):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-5, atol=2e-6)


def test_round_plan_shapes_placement_independent():
    """Plan tables must have placement-independent shapes (one compile)
    and host-first member ordering."""
    h = Hierarchy(depth=3, width=2, trainers_per_leaf=2, n_clients=20)
    rng = np.random.default_rng(1)
    p1 = rng.permutation(20)[: h.dimensions]
    p2 = rng.permutation(20)[: h.dimensions]
    plan1, plan2 = h.round_plan(p1), h.round_plan(p2)
    assert len(plan1.levels) == h.depth
    for l1, l2 in zip(plan1.levels, plan2.levels, strict=True):
        assert l1.src.shape == l2.src.shape
        np.testing.assert_array_equal(l1.seg, l2.seg)  # static segments
        np.testing.assert_array_equal(l1.n_parts, l2.n_parts)
    # deepest level: first member of each cluster is the leaf's host
    leaf = plan1.levels[0]
    starts = np.searchsorted(leaf.seg, np.arange(leaf.n_clusters))
    np.testing.assert_array_equal(leaf.src[starts], leaf.hosts)


def test_zero_round_summary_is_well_defined():
    res = FederatedRunResult(strategy="none")
    s = res.summary()
    assert s["rounds"] == 0
    assert s["total_tpd"] == 0.0 and s["mean_tpd"] == 0.0
    assert s["final_accuracy"] == 0.0
    assert all(np.isfinite(v) for v in s.values()
               if isinstance(v, float))


def test_empty_swarm_history_as_dict():
    from repro.core.pso import SwarmHistory
    d = SwarmHistory().as_dict()
    assert d == {"per_particle": [], "best": [], "worst": [], "mean": []}


# ----------------------------------------------------------------------
# the indexed input path: the sample set on the device, batch indices
# drawn on the host, the batches gathered inside ``local_all``
# ----------------------------------------------------------------------
class _HostStacked:
    """The same dataset without ``client_indices``: the orchestrator
    stacks its batches on the host, as for a dataset that cannot be
    indexed."""

    def __init__(self, data):
        self._data = data

    def __getattr__(self, name):
        if name == "client_indices":
            raise AttributeError(name)
        return getattr(self._data, name)


def _indexed_pair(n_clients=6, local_steps=2, batch_size=16):
    """Two identical orchestrators, one on the indexed path and one on
    the host-stacked path; client 1's shard is cut under the batch size,
    so the cohort has two batch-shape buckets."""
    model = get_model(get_config("mlp-smoke"))
    h = Hierarchy(depth=2, width=1, trainers_per_leaf=2,
                  n_clients=n_clients)

    def build(wrap):
        data = make_federated_dataset(get_config("mlp-smoke"),
                                      h.total_clients, seed=4)
        data.partitions[1] = data.partitions[1][:5]
        orch = FederatedOrchestrator(
            model, h, ClientPool.random(h.total_clients, seed=4),
            _HostStacked(data) if wrap else data, local_steps=local_steps,
            batch_size=batch_size, seed=4)
        orch.warmup()
        return orch

    return build(False), build(True)


def _assert_same_params(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b), strict=True):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_client_indices_gather_to_client_batch():
    """One draw rule: gathering the base set at ``client_indices`` gives
    ``client_batch`` exactly, for every client and step, a shard shorter
    than the batch included."""
    data = make_federated_dataset(get_config("mlp-smoke"), 5, seed=2)
    data.partitions[3] = data.partitions[3][:7]
    base = data.base
    for c in range(data.n_clients):
        for step in range(4):
            idx = data.client_indices(c, 16, step)
            assert idx.dtype == np.int64
            assert len(idx) == min(16, len(data.partitions[c]))
            assert np.isin(idx, data.partitions[c]).all()
            batch = data.client_batch(c, 16, step)
            np.testing.assert_array_equal(base.features[idx], batch["x"])
            np.testing.assert_array_equal(base.labels[idx], batch["y"])


def test_indexed_path_trains_like_the_host_stacked_path():
    indexed, stacked = _indexed_pair()
    tables = indexed._collect_batches(0)
    assert len(tables) == 2
    for ids, table in tables:
        assert table.dtype == np.int32
        assert table.shape[:2] == (len(ids), indexed.local_steps)
    assert all(isinstance(b, dict) for _, b in stacked._collect_batches(0))
    placement = np.arange(indexed.hierarchy.dimensions)
    # the two buckets' updates come back in client order: the reference
    # trains the same clients one by one
    ref, _ = _indexed_pair()
    rng = np.random.default_rng(4)
    for r in range(3):
        a = indexed.run_round(r, placement)
        b = stacked.run_round(r, placement)
        c = reference_round(ref, r, placement, rng)
        assert (a.tpd, a.loss, a.accuracy) == (b.tpd, b.loss, b.accuracy)
        assert (a.tpd, a.accuracy) == (c.tpd, c.accuracy)
        assert abs(a.loss - c.loss) < 5e-6
    _assert_same_params(indexed.params, stacked.params)
    # each orchestrator built only its own variant of ``local_all``
    assert list(indexed._local_fns) == [True]
    assert list(stacked._local_fns) == [False]


@pytest.mark.parametrize("cohort", ["partial", "after_admit"])
def test_train_cohort_indexed_matches_host_stacked(cohort):
    indexed, stacked = _indexed_pair()
    held = indexed._samples
    if cohort == "after_admit":
        for orch in (indexed, stacked):
            new_ids, _ = orch.admit([30.0], [8.0])
        assert list(new_ids) == [indexed.hierarchy.total_clients - 1]
        ids = np.asarray([0, 1, int(new_ids[0])])
    else:
        ids = np.asarray([1, 2, 4])
    a, ta = indexed.train_cohort(ids, 3)
    b, tb = stacked.train_cohort(ids, 3)
    np.testing.assert_array_equal(ta, tb)
    _assert_same_params(a, b)
    # the joiner's shard indexes the same base set: no second upload
    assert indexed._samples is held


def test_sample_set_uploads_once_per_base_set():
    indexed, _ = _indexed_pair(n_clients=5, local_steps=1)
    held = indexed._samples
    features, labels = indexed._device_samples()
    assert features.shape == indexed.data.base.features.shape
    assert labels.dtype == indexed.data.base.labels.dtype
    placement = np.arange(indexed.hierarchy.dimensions)
    indexed.run_round(0, placement)
    assert indexed._samples is held
    # the evaluation batch is the base set's head, sliced once
    batch = indexed._base_eval_batch(512)
    assert batch is indexed._base_eval_batch(512)
    np.testing.assert_array_equal(np.asarray(batch["x"]),
                                  indexed.data.base.features[:512])
    # a dataset over another sample set uploads that one
    other = make_federated_dataset(get_config("mlp-smoke"), 5, seed=9)
    indexed.data = other
    indexed.run_round(1, placement)
    assert indexed._samples[0] is other.base.features
    np.testing.assert_array_equal(np.asarray(indexed._device_samples()[0]),
                                  other.base.features)


@pytest.mark.parametrize("family", ["mlp", "lm"])
def test_indexed_calls_counter(family, tmp_path):
    """``round.indexed_calls`` counts every ``local_all`` dispatch fed by
    an index table: all of them for the classification set, none for an
    LM dataset, whose batches are made step by step."""
    from repro.utils import tracing
    if family == "mlp":
        orch, _ = _indexed_pair(n_clients=5, local_steps=1)
    else:
        cfg = get_config("stablelm-1.6b").reduced().replace(n_layers=1)
        h = Hierarchy(depth=1, width=2, trainers_per_leaf=2, n_clients=3)
        data = make_federated_dataset(cfg, h.total_clients, seed=1,
                                      seq_len=8)
        orch = FederatedOrchestrator(
            get_model(cfg), h, ClientPool.random(h.total_clients, seed=1),
            data, local_steps=1, batch_size=2, seed=1)
        orch.warmup()
        assert orch._device_samples() is None
    tracing.reset()
    try:
        with jax.profiler.trace(str(tmp_path)):
            orch.run_round(0, np.arange(orch.hierarchy.dimensions))
        counters = tracing.snapshot()["counters"]
    finally:
        tracing.reset()
    assert counters["round.local_calls"] >= 1
    want = counters["round.local_calls"] if family == "mlp" else 0
    assert counters.get("round.indexed_calls", 0) == want
