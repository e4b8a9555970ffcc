"""Elastic EMULATED track: orchestrator-level client admit/retire.

Covers the PR-5 guarantees:

* ``FederatedOrchestrator.admit``/``retire`` resize the LIVE training
  population — joiners get fresh data shards and train their first
  local step from the CURRENT global model (never round-0 init),
  survivors keep their exact shards across renumbering;
* retiring a current aggregator host yields a repaired, valid placement
  for the very next round (same ``slot_remap``/``repair_placement``
  machinery as the simulated track);
* emulated-vs-simulated elastic PARITY: one event schedule replays the
  identical hierarchy sequence, ``topology_version`` trace and
  placement-repair decisions on both tracks;
* the batched round engine is retargeted across resizes with its
  segment-sum executables re-jitted only when the tree shape actually
  changed (and reused when an oscillating population returns);
* the elastic presets run end-to-end on the emulated environment and
  write schema-v2 artifacts whose ``topology_version`` series shows the
  re-hierarchizations.
"""
import jax
import numpy as np
import pytest
from _round_reference import reference_round

from repro.configs import get_config
from repro.core.hierarchy import ClientPool, Hierarchy
from repro.core.registry import create_strategy
from repro.data.synthetic import FederatedDataset, FederatedLMDataset
from repro.experiments import get_scenario, run_experiment
from repro.experiments.results import validate_result_dict
from repro.experiments.runner import _EVENT_STREAM
from repro.experiments.scenarios import ClientJoin, ClientLeave
from repro.fl.aggregation import SegmentAggregator
from repro.fl.orchestrator import FederatedOrchestrator
from repro.models import get_model


def make_orchestrator(n_clients=10, seed=0, local_steps=2,
                      depth=2, width=2, tpl=1):
    cfg = get_config("mlp-smoke")
    model = get_model(cfg)
    h = Hierarchy(depth, width, tpl, n_clients=n_clients)
    pool = ClientPool.random(n_clients, seed=seed)
    data = FederatedDataset.make(n_clients, seed=seed)
    return FederatedOrchestrator(model, h, pool, data, local_steps=local_steps,
                                 batch_size=8, seed=seed)


def tree_allclose(a, b):
    return all(np.allclose(x, y) for x, y in
               zip(jax.tree.leaves(a), jax.tree.leaves(b), strict=True))


# ---------------------------------------------------------------------------
# admit: joiner initialization + data provisioning
# ---------------------------------------------------------------------------
def manual_local_steps(orch, params, client_id, round_idx):
    """A client's local update recomputed from first principles."""
    grad = jax.value_and_grad(orch.model.loss_fn, has_aux=True)
    for s in range(orch.local_steps):
        batch = orch.data.client_batch(
            client_id, orch.batch_size,
            round_idx * orch.local_steps + s)
        _, grads = grad(params, batch)
        params = jax.tree.map(lambda p, g: p - orch.local_lr * g,
                              params, grads)
    return params


def test_joiner_first_step_starts_from_current_global_model():
    orch = make_orchestrator()
    init_params = jax.tree.map(np.copy, orch.params)
    orch.warmup()
    for r in range(2):
        orch.run_round(r, np.arange(orch.hierarchy.dimensions))
    global_before = jax.tree.map(np.copy, orch.params)
    # the federation has actually moved off init by round 2
    assert not tree_allclose(global_before, init_params)

    ids, update = orch.admit(memcap=[25.0], pspeed=[9.0])
    joiner = int(ids[0])
    assert joiner == 10
    assert orch.data.n_clients == 11               # shard provisioned
    assert len(orch.data.partitions[joiner]) >= 8

    got = manual_local_steps(orch, orch.params, joiner, 2)
    from_global = manual_local_steps(orch, global_before, joiner, 2)
    from_init = manual_local_steps(orch, init_params, joiner, 2)
    assert tree_allclose(got, from_global)         # trains from global...
    assert not tree_allclose(got, from_init)       # ...NOT from init

    # and the joiner's update is what the next round actually consumes
    stacked, _ = orch.train_cohort([joiner], 2)
    p_new = jax.tree.map(lambda x: x[0], stacked)
    assert tree_allclose(p_new, from_global)


def test_admit_returns_update_and_next_round_runs():
    records = {}
    for engine in ("orchestrator", "reference"):
        orch = make_orchestrator()
        strat = create_strategy("static", orch.hierarchy, seed=0,
                                placement=[0, 1, 2])
        rng = np.random.default_rng(0)
        if engine == "orchestrator":
            orch.warmup()
            step = orch.run_round
        else:
            def step(r, p, orch=orch, rng=rng):
                return reference_round(orch, r, p, rng)
        step(0, strat.propose(0))
        ids, update = orch.admit(memcap=[20.0, 30.0], pspeed=[7.0, 9.0])
        assert update is not None                  # 12 > capacity 10
        strat.migrate(update)
        p1 = np.asarray(strat.propose(1), np.int64)
        orch.hierarchy.validate_placement(p1)
        rec1 = step(1, p1)
        assert np.isfinite(rec1.tpd) and rec1.tpd > 0
        assert len(rec1.placement) == orch.hierarchy.dimensions
        records[engine] = rec1
    a, b = records["orchestrator"], records["reference"]
    assert a.placement == b.placement
    assert a.tpd == b.tpd


def test_retiring_an_aggregator_host_repairs_next_round():
    orch = make_orchestrator(n_clients=12, depth=2, width=2, tpl=2)
    strat = create_strategy("static", orch.hierarchy, seed=3,
                            placement=[4, 7, 2])
    orch.warmup()
    p0 = np.asarray(strat.propose(0), np.int64)
    orch.run_round(0, p0)
    victim = int(p0[0])                            # the ROOT aggregator
    update = orch.retire([victim])
    assert update is not None
    assert update.client_remap[victim] == -1
    strat.migrate(update)
    p1 = np.asarray(strat.propose(1), np.int64)
    orch.hierarchy.validate_placement(p1)          # repaired + valid
    orch.run_round(1, p1)                          # the very next round runs
    # surviving hosts were carried through the id renumbering
    for old_slot, old_host in enumerate(p0[1:], start=1):
        if update.slot_remap is not None:
            new_ids = np.where(update.slot_remap == old_slot)[0]
            for s in new_ids:
                assert p1[s] == update.client_remap[old_host]


def test_unsynced_resize_fails_loud_at_round_time():
    orch = make_orchestrator()
    orch.warmup()
    orch.run_round(0, np.arange(3))
    orch.clients.join(memcap=[20.0], pspeed=[8.0])
    with pytest.raises(RuntimeError, match="sync_population"):
        orch.run_round(1, np.arange(3))
    orch.sync_population()
    # synced: the next round is valid again
    orch.run_round(1, np.arange(orch.hierarchy.dimensions))


# ---------------------------------------------------------------------------
# retire: survivors keep their data shards
# ---------------------------------------------------------------------------
def test_survivor_shards_are_carried_across_renumbering():
    orch = make_orchestrator(n_clients=10)
    before = {i: orch.data.partitions[i].copy() for i in range(10)}
    update = orch.retire([3, 7])
    remap = update.client_remap
    assert orch.data.n_clients == 8
    for old in range(10):
        if old in (3, 7):
            continue
        np.testing.assert_array_equal(
            orch.data.partitions[int(remap[old])], before[old])
    # weights re-normalized over the survivors
    w = orch.weights
    assert len(w) == 8 and abs(float(np.sum(w)) - 1.0) < 1e-5


def test_survivor_batch_streams_survive_renumbering():
    """Renumbering must not move a survivor onto another client's
    batch-draw sequence, nor recycle a departed client's stream onto a
    joiner (same invariant the LM dataset pins via stream ids)."""
    data = FederatedDataset.make(6, seed=0)
    before = {i: data.client_batch(i, 4, step=3) for i in range(6)}
    remap = np.array([0, -1, 1, 2, 3, 4])          # client 1 departs
    data.resize(remap, 6, np.random.default_rng(0))  # +1 joiner at id 5
    for old, new in ((0, 0), (2, 1), (3, 2), (4, 3), (5, 4)):
        np.testing.assert_array_equal(
            data.client_batch(new, 4, step=3)["x"], before[old]["x"])
    assert data.stream_of == [0, 2, 3, 4, 5, 6]    # 1 retired, 6 minted


def test_federated_dataset_resize_provisions_joiners():
    data = FederatedDataset.make(6, seed=0)
    rng = np.random.default_rng(0)
    data.resize(None, 9, rng)
    assert data.n_clients == 9
    labels = data.base.labels
    for i in (6, 7, 8):
        part = data.partitions[i]
        assert len(part) >= 8
        assert part.min() >= 0 and part.max() < len(labels)
    # deterministic: same rng stream -> same shards
    data2 = FederatedDataset.make(6, seed=0)
    data2.resize(None, 9, np.random.default_rng(0))
    for i in range(9):
        np.testing.assert_array_equal(data.partitions[i],
                                      data2.partitions[i])


def test_lm_dataset_streams_survive_renumbering():
    data = FederatedLMDataset(vocab_size=64, seq_len=8, n_clients_=5, seed=1)
    before = {i: data.client_batch(i, 4, 0) for i in range(5)}
    remap = np.array([0, -1, 1, 2, 3])             # client 1 departs
    data.resize(remap, 5)                          # +1 joiner at id 4
    assert data.n_clients == 5
    for old, new in ((0, 0), (2, 1), (3, 2), (4, 3)):
        np.testing.assert_array_equal(
            data.client_batch(new, 4, 0)["tokens"],
            before[old]["tokens"])
    # the joiner minted a FRESH stream, not the departed client's
    joiner = data.client_batch(4, 4, 0)["tokens"]
    assert not np.array_equal(joiner, before[1]["tokens"])
    assert data.stream_of == [0, 2, 3, 4, 5]


# ---------------------------------------------------------------------------
# engine retargeting: re-jit only on tree-shape change
# ---------------------------------------------------------------------------
def test_segment_aggregator_rejits_only_on_shape_change():
    agg = SegmentAggregator(Hierarchy(3, 2, 2, n_clients=15))
    fns = agg._level_fns
    # in-window growth: same tree shape, nothing recompiled
    assert agg.retarget(Hierarchy(3, 2, 2, n_clients=19)) is False
    assert agg._level_fns is fns
    # structural change: executables swap
    assert agg.retarget(Hierarchy(2, 3, 4, n_clients=19)) is True
    assert agg._level_fns is not fns
    # oscillating back reuses the cached compiled functions
    first_shape_fns = list(fns)
    assert agg.retarget(Hierarchy(3, 2, 2, n_clients=16)) is True
    assert agg._level_fns == first_shape_fns


def test_batched_and_loop_engines_agree_across_a_resize():
    """The round engine matches the reference before and after an
    admission grows the tree."""
    records = {}
    for engine in ("orchestrator", "reference"):
        orch = make_orchestrator()
        rng = np.random.default_rng(0)
        if engine == "orchestrator":
            orch.warmup()
            step = orch.run_round
        else:
            def step(r, p, orch=orch, rng=rng):
                return reference_round(orch, r, p, rng)
        recs = [step(0, np.arange(3))]
        orch.admit(memcap=[20.0, 30.0, 40.0], pspeed=[7.0, 8.0, 9.0])
        dims = orch.hierarchy.dimensions
        recs.append(step(1, np.arange(dims)))
        records[engine] = recs
    for a, b in zip(records["orchestrator"], records["reference"],
                    strict=True):
        assert a.tpd == pytest.approx(b.tpd, rel=1e-5)
        assert a.loss == pytest.approx(b.loss, rel=1e-4)


# ---------------------------------------------------------------------------
# emulated-vs-simulated elastic parity
# ---------------------------------------------------------------------------
def drive_trace(spec, strategy_name, rounds, seed=0):
    """The run_single loop, instrumented to capture per-round topology
    and placement decisions."""
    env = spec.make_environment(seed)
    kw = {"placement": list(range(env.hierarchy.dimensions))} \
        if strategy_name == "static" else {}
    strat = create_strategy(strategy_name, env.hierarchy, seed=seed,
                            clients=env.clients,
                            cost_model=env.cost_model, **kw)
    events = spec.make_events()
    erng = np.random.default_rng((seed, _EVENT_STREAM))
    env.begin()
    trace = []
    for r in range(rounds):
        for ev in events:
            ev.on_round(r, env.clients, erng)
        update = env.sync_topology()
        if update is not None:
            strat.migrate(update)
            for ev in events:
                ev.on_topology(update)
        p = np.asarray(strat.propose(r), np.int64)
        obs = env.step(r, p)
        strat.observe(p, obs.tpd)
        trace.append((obs.topology_version,
                      (env.hierarchy.depth, env.hierarchy.width,
                       env.hierarchy.total_clients),
                      p.tolist()))
    return trace


@pytest.mark.parametrize("strategy", ["static", "random"])
def test_emulated_matches_simulated_hierarchy_and_repairs(strategy):
    """One event schedule -> the same hierarchy sequence,
    topology_version trace AND placement(-repair) decisions on both
    tracks (the observed TPDs differ; the topology machinery must not).
    """
    sim = get_scenario("ebb-and-flow").with_overrides(
        events=(ClientJoin(every=2, count=10, first_round=1),
                ClientLeave(every=3, count=9, first_round=2,
                            min_clients=11)))
    emu = sim.for_env("emulated").with_overrides(
        model="mlp-smoke", local_steps=1, batch_size=8)
    t_sim = drive_trace(sim, strategy, rounds=8)
    t_emu = drive_trace(emu, strategy, rounds=8)
    assert t_sim == t_emu
    assert max(tv for tv, _, _ in t_sim) >= 2      # actually elastic


def test_for_env_roundtrip_and_validation():
    spec = get_scenario("flash-crowd")
    assert spec.for_env("simulated") is spec
    emu = spec.for_env("emulated")
    assert emu.kind == "emulated" and emu.name == spec.name
    assert emu.for_env("simulated").kind == "simulated"
    with pytest.raises(ValueError, match="unknown environment kind"):
        spec.for_env("docker")


# ---------------------------------------------------------------------------
# end-to-end: elastic presets on the emulated track, schema-v2 artifact
# ---------------------------------------------------------------------------
def test_flash_crowd_emulated_end_to_end(tmp_path):
    spec = get_scenario("flash-crowd").for_env("emulated").with_overrides(
        model="mlp-smoke", local_steps=1, batch_size=8,
        events=(ClientJoin(every=2, count=8, first_round=1,
                           last_round=3),))
    res = run_experiment(spec, ["pso", "random"], rounds=5, seeds=(0,),
                         progress=False)
    out = res.save(tmp_path / "flash_crowd_emu.json")
    d = res.to_dict()
    assert d["schema_version"] == 3
    assert validate_result_dict(d) == []
    for run in res.runs:
        tv = run.metrics["topology_version"]
        assert len(tv) == 5
        assert max(tv) >= 1                        # >=1 re-hierarchization
        assert all(b >= a for a, b in zip(tv, tv[1:], strict=False))
        # the emulated track's training metrics ride along
        assert len(run.metrics["accuracy"]) == 5
        assert len(run.metrics["n_clients"]) == 5
        assert run.metrics["n_clients"][-1] > run.metrics["n_clients"][0]
    assert out.exists()


def test_emulated_elastic_events_log_topology_lines():
    spec = get_scenario("ebb-and-flow").for_env("emulated").with_overrides(
        model="mlp-smoke", local_steps=1, batch_size=8,
        events=(ClientJoin(every=2, count=10, first_round=1),
                ClientLeave(every=2, count=10, first_round=2,
                            min_clients=11)))
    res = run_experiment(spec, ["pso"], rounds=5, seeds=(0,),
                         progress=False)
    log = res.runs[0].event_log
    assert any("topology v1" in line for line in log)
    assert any("join:" in line for line in log)
    assert any("leave:" in line for line in log)
