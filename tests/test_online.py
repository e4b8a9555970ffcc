"""The asynchronous online track: virtual clock, seeded arrivals,
staleness-weighted async FedAvg (fast vs. scalar oracle — the
registered parity pairs ``staleness_weights`` / ``_staleness_weights_ref``
and ``async_merge_batched`` / ``_async_merge_ref``), count-or-deadline
buffers, and the event-driven ``OnlineEnvironment`` (overlapping
rounds, bit-identical replays, delay-triggered mid-round placement
re-optimization, elastic populations)."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import create_strategy
from repro.core.hierarchy import ClientPool, Hierarchy
from repro.data.synthetic import make_federated_dataset
from repro.experiments import (
    OnlineEnvironment,
    get_scenario,
    run_experiment,
)
from repro.fl.orchestrator import FederatedOrchestrator
from repro.models import get_model
from repro.online import (
    AggregatorBuffer,
    ArrivalProcess,
    AsyncConfig,
    BufferedPart,
    BufferEntry,
    VirtualClock,
    async_merge_batched,
    flush_count,
    staleness_weights,
)
from repro.online.async_fedavg import _async_merge_ref, _staleness_weights_ref


# ---------------------------------------------------------------------------
# virtual clock
# ---------------------------------------------------------------------------
def test_clock_pops_in_time_order_and_advances_now():
    clk = VirtualClock()
    clk.schedule(2.0, "late")
    clk.schedule(1.0, "early")
    clk.schedule(3.0, "last")
    assert clk.pop() == (1.0, "early")
    assert clk.now == 1.0
    assert clk.pop() == (2.0, "late")
    assert clk.pop() == (3.0, "last")
    assert not clk


def test_clock_ties_break_by_schedule_order_fifo():
    # events landing on the SAME instant pop in schedule order — the
    # deterministic tie-break the whole track leans on; the payloads
    # are plain strings precisely because the heap must never compare
    # them
    clk = VirtualClock()
    for i in range(8):
        clk.schedule(5.0, f"ev{i}")
    assert [clk.pop()[1] for i in range(8)] == [f"ev{i}" for i in range(8)]


def test_clock_refuses_scheduling_into_the_past():
    clk = VirtualClock()
    clk.schedule(1.0, "a")
    clk.pop()
    with pytest.raises(ValueError, match="past"):
        clk.schedule(0.5, "b")


def test_clock_advance_to_is_monotone():
    clk = VirtualClock()
    clk.advance_to(4.0)
    assert clk.now == 4.0
    with pytest.raises(ValueError, match="rewind"):
        clk.advance_to(2.0)


def test_clock_replace_preserves_relative_order():
    clk = VirtualClock()
    clk.schedule(2.0, "b")
    clk.schedule(1.0, "a")
    clk.schedule(1.0, "a2")
    pend = clk.pending()
    clk.replace([row for row in pend if row[2] != "b"])
    assert [clk.pop()[1] for _ in range(2)] == ["a", "a2"]


# ---------------------------------------------------------------------------
# seeded arrivals
# ---------------------------------------------------------------------------
def test_arrival_zero_sigma_is_exactly_one_and_stateless():
    ap = ArrivalProcess(seed=7, sigma=0.0)
    assert all(ap.factor(c) == 1.0 for c in range(5))
    assert not ap._rngs  # no stream ever created — the degenerate pin


def test_arrival_same_seed_same_factors_any_call_order():
    a = ArrivalProcess(seed=3, sigma=0.4)
    b = ArrivalProcess(seed=3, sigma=0.4)
    # a draws clients 0..4 in order; b interleaves — per-client streams
    # make the sequences identical anyway
    fa = {c: [a.factor(c) for _ in range(3)] for c in range(5)}
    fb = {}
    for k in range(3):
        for c in (4, 2, 0, 3, 1):
            fb.setdefault(c, []).append(b.factor(c))
    assert fa == fb
    assert ArrivalProcess(seed=4, sigma=0.4).factor(0) != fa[0][0]


def test_arrival_migrate_carries_streams_across_renumbering():
    a = ArrivalProcess(seed=3, sigma=0.4)
    first = [a.factor(c) for c in range(4)]  # noqa: F841 — advance streams
    nxt = {c: a.factor(c) for c in range(4)}

    b = ArrivalProcess(seed=3, sigma=0.4)
    for c in range(4):
        b.factor(c)
    # client 1 departs; 0 stays, 2->1, 3->2
    b.migrate(np.array([0, -1, 1, 2]))
    assert b.factor(0) == nxt[0]
    assert b.factor(1) == nxt[2]
    assert b.factor(2) == nxt[3]


# ---------------------------------------------------------------------------
# staleness weighting (registered parity pair)
# ---------------------------------------------------------------------------
def test_staleness_weights_hand_computed():
    # w = (2, 1), s = (0, 1), alpha = 1: decayed = (2, 0.5), sum 2.5
    w = staleness_weights([2.0, 1.0], [0.0, 1.0], alpha=1.0)
    assert np.allclose(w, [0.8, 0.2])
    # alpha = 0 ignores staleness entirely: plain normalized weights
    w0 = staleness_weights([2.0, 1.0], [0.0, 7.0], alpha=0.0)
    assert np.allclose(w0, [2.0 / 3.0, 1.0 / 3.0])
    # alpha = 0.5, s = 3: decay factor (1+3)^-0.5 = 0.5 exactly
    w5 = staleness_weights([1.0, 1.0], [0.0, 3.0], alpha=0.5)
    assert np.allclose(w5, [2.0 / 3.0, 1.0 / 3.0])


def test_staleness_weights_match_scalar_reference():
    rng = np.random.default_rng(0)
    for n in (1, 3, 17):
        base = rng.uniform(0.1, 2.0, n)
        stale = rng.integers(0, 9, n).astype(float)
        for alpha in (0.0, 0.5, 1.7):
            fast = staleness_weights(base, stale, alpha)
            ref = _staleness_weights_ref(base, stale, alpha)
            assert np.allclose(fast, ref, rtol=1e-12, atol=1e-15)
            assert fast.sum() == pytest.approx(1.0)


def test_staleness_weights_validation():
    with pytest.raises(ValueError, match="negative"):
        staleness_weights([1.0], [-1.0], alpha=0.5)
    with pytest.raises(ValueError, match="vs"):
        staleness_weights([1.0, 1.0], [0.0], alpha=0.5)


# ---------------------------------------------------------------------------
# the root merge (registered parity pair)
# ---------------------------------------------------------------------------
def _tree(rng, k=None):
    def leaf(*shape):
        x = rng.standard_normal(shape).astype(np.float32)
        return jnp.asarray(x)
    if k is None:
        return {"w": leaf(4, 3), "b": leaf(3)}
    return {"w": leaf(k, 4, 3), "b": leaf(k, 3)}


def test_async_merge_matches_scalar_reference():
    rng = np.random.default_rng(1)
    k = 5
    g = _tree(rng)
    stacked = _tree(rng, k)
    updates = [jax.tree.map(lambda x, i=i: x[i], stacked) for i in range(k)]
    base = rng.uniform(0.5, 1.5, k)
    stale = np.array([0.0, 2.0, 0.0, 5.0, 1.0])
    for alpha, eta in ((0.5, 1.0), (1.0, 0.6)):
        fast = async_merge_batched(g, stacked, base, stale, alpha, eta)
        ref = _async_merge_ref(g, updates, base, stale, alpha, eta)
        for lf, lr in zip(jax.tree.leaves(fast), jax.tree.leaves(ref),
                          strict=True):
            assert np.allclose(lf, lr, rtol=1e-5, atol=1e-6)


def test_async_merge_zero_staleness_eta_one_is_weighted_fedavg():
    # the degenerate corner: full cohort, nothing stale, full server
    # step — the merge must equal the plain weighted average of the
    # updates (what a synchronous round commits)
    rng = np.random.default_rng(2)
    k = 4
    g = _tree(rng)
    stacked = _tree(rng, k)
    base = rng.uniform(0.5, 1.5, k)
    out = async_merge_batched(g, stacked, base, np.zeros(k), 0.5, 1.0)
    wn = base / base.sum()
    for lo, ls in zip(jax.tree.leaves(out), jax.tree.leaves(stacked),
                      strict=True):
        expect = np.tensordot(wn.astype(np.float32), np.asarray(ls),
                              axes=(0, 0))
        assert np.allclose(lo, expect, rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# count-or-deadline buffers
# ---------------------------------------------------------------------------
def test_flush_count_thresholds():
    assert flush_count(4, 1.0) == 4
    assert flush_count(4, 0.75) == 3
    assert flush_count(4, 0.5) == 2
    assert flush_count(4, 0.01) == 1
    assert flush_count(4, 2.0) == 4      # clamped to expected
    assert flush_count(1, 0.0) == 1      # never zero
    with pytest.raises(ValueError):
        flush_count(0, 1.0)


def _part(c, v=0):
    return BufferedPart(src=c, entries=(BufferEntry(c, v),))


def test_buffer_count_flush_path():
    buf = AggregatorBuffer(slot=0, expected=4, threshold=3)
    assert not buf.deposit(_part(0))
    assert not buf.deposit(_part(1))
    assert buf.deposit(_part(2))     # threshold met -> flush now
    drained = buf.take()
    assert [p.src for p in drained] == [0, 1, 2]
    assert buf.empty and buf.epoch == 1


def test_buffer_epoch_guards_stale_deadlines():
    # arm a deadline at epoch 0, count-flush first, then the deadline
    # fires against epoch 1 — the guard the environment checks
    buf = AggregatorBuffer(slot=2, expected=2, threshold=2)
    buf.deposit(_part(0))
    armed_epoch = buf.epoch
    assert buf.deposit(_part(1))
    buf.take()
    buf.deposit(_part(2))            # next cohort starts filling
    assert buf.epoch != armed_epoch  # stale deadline must be dropped
    assert not buf.empty


# ---------------------------------------------------------------------------
# the environment: overlap, determinism, re-optimization, elasticity
# ---------------------------------------------------------------------------
def _online_env(async_cfg, seed=0, n_clients=10, depth=2, width=2,
                tpl=1, pspeed=None):
    cfg = get_config("mlp-smoke")
    h = Hierarchy(depth=depth, width=width, trainers_per_leaf=tpl,
                  n_clients=n_clients)
    if pspeed is None:
        pool = ClientPool.random(h.total_clients, seed=seed)
    else:
        pool = ClientPool(
            memcap=np.full(n_clients, 1024.0),
            pspeed=np.asarray(pspeed, np.float64),
            mdatasize=np.full(n_clients, 5.0))
    data = make_federated_dataset(cfg, h.total_clients, seed=seed)
    orch = FederatedOrchestrator(get_model(cfg), h, pool, data,
                                 local_steps=1, batch_size=16, seed=seed,
                                 comm_latency=0.002)
    env = OnlineEnvironment(orch, async_cfg, seed=seed)
    env.begin()
    return env


def test_online_rounds_overlap_and_staleness_accrues():
    env = _online_env(AsyncConfig(jitter=0.35, flush_fraction=0.75,
                                  flush_timeout=0.5, server_lr=0.7))
    placement = np.array([0, 1, 2])
    obs = [env.step(r, placement) for r in range(6)]
    overlaps = [o.metrics["overlap"] for o in obs]
    stales = [o.metrics["staleness_max"] for o in obs]
    assert all(o.tpd > 0 for o in obs)
    assert max(overlaps) > 0            # some round dispatched a partial
    assert max(stales) > 0              # some update landed late
    assert all(o.metrics["merged"] >= 1 for o in obs)
    # flushes really went through both trigger paths somewhere
    log = "\n".join(line for o in obs for line in o.log)
    assert "flush[deadline]" in log
    assert "root merge" in log


def test_online_same_seed_runs_are_bit_identical():
    spec = get_scenario("online-fig4").with_overrides(model="mlp-smoke")
    arts = []
    for _ in range(2):
        res = run_experiment(spec, ["pso"], rounds=4, seeds=[0],
                             progress=False)
        arts.append(json.dumps(res.to_dict(), sort_keys=True))
    # event trace, staleness series, tpds, placements: all of it
    assert arts[0] == arts[1]


def test_online_reopt_swaps_host_mid_round():
    # a host that turns straggler mid-run: its flush latency blows past
    # the threshold x EWMA trigger and the environment swaps the slot's
    # host for the fastest OBSERVED unplaced client — off the round
    # boundary, placement differing from the strategy's proposal
    env = _online_env(
        AsyncConfig(jitter=0.1, flush_fraction=0.75, flush_timeout=0.5,
                    server_lr=0.7, reopt_threshold=2.0, reopt_beta=0.5),
        pspeed=[10.0, 10.0, 10.0] + [8.0] * 7)
    proposal = np.array([0, 1, 2])
    for r in range(3):                    # settle the EWMAs
        obs = env.step(r, proposal)
        assert np.array_equal(obs.placement, proposal)
    assert env._reopt_swaps == 0
    env.clients.pspeed[0] = 0.05          # root host hits the wall
    swapped_round = None
    for r in range(3, 8):
        obs = env.step(r, proposal)
        if obs.metrics["reopt_swaps"] > 0:
            swapped_round = r
            break
    assert swapped_round is not None
    assert not np.array_equal(obs.placement, proposal)  # mid-round change
    assert obs.placement[0] != 0
    assert any("REOPT" in line for line in obs.log)
    # the swap pulses the elastic machinery: an identity TopologyUpdate
    # with a bumped version, same hierarchy, no client remap
    update = env.sync_topology()
    assert update is not None
    assert update.client_remap is None
    assert update.new_hierarchy is env.hierarchy
    assert update.version == env.topology_version
    assert env.sync_topology() is None    # pulse is one-shot


def test_online_elastic_population_grows_mid_run():
    spec = get_scenario("online-fig4").with_overrides(
        model="mlp-smoke",
        events='[{"event": "ClientJoin", "every": 3, "count": 6, '
               '"first_round": 2}]')
    arts = []
    for _ in range(2):
        res = run_experiment(spec, ["pso"], rounds=6, seeds=[0],
                             progress=False)
        arts.append(json.dumps(res.to_dict(), sort_keys=True))
    assert arts[0] == arts[1]             # elastic + async, still replayable
    run = res.runs[0]
    assert run.metrics["n_clients"][0] == 10.0
    assert run.metrics["n_clients"][-1] > 10.0
    assert max(run.metrics["topology_version"]) >= 1.0


def test_orchestrator_refuses_removed_engine_and_timing():
    """One round engine, one timing rule: the per-client loop engine and
    wall-clock timing are refused by name."""
    cfg = get_config("mlp-smoke")
    h = Hierarchy(depth=2, width=2, trainers_per_leaf=1, n_clients=10)
    pool = ClientPool.random(h.total_clients, seed=0)
    data = make_federated_dataset(cfg, h.total_clients, seed=0)
    for kw, name in (({"engine": "loop"}, "'loop'"),
                     ({"timing": "measured"}, "'measured'")):
        with pytest.raises(ValueError, match=name):
            FederatedOrchestrator(get_model(cfg), h, pool, data,
                                  local_steps=1, batch_size=16, seed=0, **kw)


def test_online_strategy_protocol_unmodified():
    """The same PlacementStrategy class drives the online world through
    the identical propose/observe loop (the API contract)."""
    env = _online_env(AsyncConfig(jitter=0.2, flush_fraction=0.75,
                                  flush_timeout=0.5))
    strat = create_strategy("pso", env.hierarchy, seed=0)
    for r in range(2):
        p = np.asarray(strat.propose(r), np.int64)
        obs = env.step(r, p)
        assert obs.tpd > 0
        strat.observe(p, obs.tpd)
    assert strat.pso.evaluations == 2
