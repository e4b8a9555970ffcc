"""Environment parity: the unified propose/observe protocol reproduces
the two historical code paths bit-for-bit.

* SimulatedEnvironment == driving the strategy against CostModel.tpd
  directly, and its cost model reproduces the FlagSwapPSO.run (Fig. 3)
  trajectory exactly;
* EmulatedEnvironment == FederatedOrchestrator.run records;
* the refactored Fig. 4 bench path (run_experiment on paper-fig4)
  equals a seed-era hand-built orchestrator loop.
"""
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import create_strategy
from repro.core.cost_model import CostModel
from repro.core.hierarchy import ClientPool, Hierarchy
from repro.core.pso import FlagSwapPSO
from repro.data.synthetic import make_federated_dataset
from repro.experiments import (
    EmulatedEnvironment,
    SimulatedEnvironment,
    get_scenario,
    run_experiment,
)
from repro.fl.orchestrator import FederatedOrchestrator
from repro.models import get_model


def test_simulated_env_matches_direct_cost_model_loop():
    h = Hierarchy(depth=3, width=2, trainers_per_leaf=2)
    pool = ClientPool.random(h.total_clients, seed=0)
    cm = CostModel(h, pool)

    # seed-era loop: strategy straight against cm.tpd
    ref = create_strategy("pso", h, seed=0)
    ref_tpds = []
    for r in range(60):
        p = ref.propose(r)
        t = cm.tpd(p)
        ref.observe(p, t)
        ref_tpds.append(t)

    # same strategy through the environment protocol
    env = SimulatedEnvironment(h, ClientPool.random(h.total_clients,
                                                    seed=0))
    strat = create_strategy("pso", h, seed=0)
    env_tpds = []
    env.begin()
    for r in range(60):
        p = np.asarray(strat.propose(r), np.int64)
        obs = env.step(r, p)
        strat.observe(p, obs.tpd)
        env_tpds.append(obs.tpd)

    assert env_tpds == ref_tpds  # bit-for-bit, no tolerance


def test_simulated_env_cost_model_reproduces_fig3_pso_run():
    # the Fig. 3 swarm-mode drive through the scenario environment must
    # equal direct CostModel construction, history and all
    spec = get_scenario("paper-fig3").with_overrides(depth=3, width=4)
    env = spec.make_environment(seed=0)

    h = Hierarchy(depth=3, width=4, trainers_per_leaf=2)
    pool = ClientPool.random(h.total_clients, seed=0)
    cm = CostModel(h, pool)

    pso_ref = FlagSwapPSO(h.dimensions, h.total_clients, n_particles=5,
                          seed=0)
    best_ref = pso_ref.run(cm.fitness, iterations=25,
                           batch_fitness_fn=cm.batch_fitness)

    pso_env = FlagSwapPSO(env.hierarchy.dimensions,
                          env.hierarchy.total_clients, n_particles=5,
                          seed=0)
    best_env = pso_env.run(env.cost_model.fitness, iterations=25,
                           batch_fitness_fn=env.cost_model.batch_fitness)

    assert np.array_equal(best_ref, best_env)
    assert pso_ref.gbest_f == pso_env.gbest_f
    assert pso_ref.history.best == pso_env.history.best
    assert pso_ref.history.mean == pso_env.history.mean


@pytest.fixture(scope="module")
def emu_setup():
    cfg = get_config("paper-mlp-1m8")
    h = Hierarchy(depth=2, width=2, trainers_per_leaf=2, n_clients=11)
    return cfg, h


def _fresh_orchestrator(cfg, h, seed=0):
    model = get_model(cfg)
    clients = ClientPool.random(h.total_clients, seed=seed)
    data = make_federated_dataset(cfg, h.total_clients, seed=seed)
    return FederatedOrchestrator(model, h, clients, data, local_steps=1,
                                 batch_size=16, seed=seed)


def test_emulated_env_matches_orchestrator_run(emu_setup):
    cfg, h = emu_setup
    rounds = 3

    orch_ref = _fresh_orchestrator(cfg, h)
    strat_ref = create_strategy("pso", h, seed=0)
    res_ref = orch_ref.run(strat_ref, rounds=rounds)

    env = EmulatedEnvironment(_fresh_orchestrator(cfg, h))
    strat = create_strategy("pso", h, seed=0)
    env.begin()
    records = []
    for r in range(rounds):
        p = np.asarray(strat.propose(r), np.int64)
        obs = env.step(r, p)
        strat.observe(p, obs.tpd)
        records.append(obs)

    for ref, obs in zip(res_ref.rounds, records, strict=True):
        assert obs.tpd == ref.tpd
        assert obs.placement.tolist() == ref.placement
        assert obs.metrics["loss"] == ref.loss
        assert obs.metrics["accuracy"] == ref.accuracy
        assert obs.metrics["train_time"] == ref.train_time
        assert obs.metrics["agg_time"] == ref.agg_time


def test_fig4_experiment_matches_seed_era_bench(emu_setup):
    """run_experiment('paper-fig4') == the pre-refactor bench loop."""
    rounds = 3
    # seed-era bench_fig4_cluster.run_strategy, verbatim reconstruction
    cfg = get_config("paper-mlp-1m8")
    h = Hierarchy(depth=2, width=2, trainers_per_leaf=1, n_clients=10)
    pool = ClientPool(
        memcap=np.array([2048.0, 1024.0, 1024.0] + [64.0] * 7),
        pspeed=np.array([4.0, 2.0, 2.0] + [1.0] * 7),
        mdatasize=np.full(10, 30.0))
    ref = {}
    for name in ("pso", "random"):
        model = get_model(cfg)
        data = make_federated_dataset(cfg, h.total_clients, seed=0)
        strat = create_strategy(name, h, seed=0, clients=pool,
                                cost_model=CostModel(h, pool))
        orch = FederatedOrchestrator(model, h, pool, data, local_steps=2,
                                     batch_size=32, seed=0,
                                     comm_latency=0.002)
        ref[name] = orch.run(strat, rounds=rounds)

    result = run_experiment("paper-fig4", ["pso", "random"],
                            rounds=rounds, seeds=[0], progress=False)
    for name in ("pso", "random"):
        new_run = result.runs_for(name)[0]
        assert new_run.tpds == ref[name].tpds.tolist()
        assert new_run.metrics["accuracy"] == \
            [r.accuracy for r in ref[name].rounds]
    # headline direction is preserved by bit-identical trajectories; the
    # full-length ordering claim rides the same code path
    assert result.aggregates["pso"]["total_tpd"] == \
        pytest.approx(ref["pso"].total_processing_time)


def test_degenerate_online_env_matches_emulated_bit_for_bit(emu_setup):
    """The online track's parity pin: zero jitter + full-cohort flushes
    + no deadline routes every round through the orchestrator's own
    train/aggregate executables — the asynchronous world degenerates to
    lockstep and reproduces EmulatedEnvironment exactly (tpd, losses,
    accuracies, train/agg split), while the virtual clock still streams
    the arrival events underneath."""
    from repro.experiments import OnlineEnvironment
    from repro.online import AsyncConfig
    cfg, h = emu_setup
    rounds = 3

    env_ref = EmulatedEnvironment(_fresh_orchestrator(cfg, h))
    env_onl = OnlineEnvironment(_fresh_orchestrator(cfg, h),
                                AsyncConfig(), seed=0)
    assert AsyncConfig().degenerate
    obs_ref, obs_onl = [], []
    for env, out in ((env_ref, obs_ref), (env_onl, obs_onl)):
        strat = create_strategy("pso", h, seed=0)
        env.begin()
        for r in range(rounds):
            p = np.asarray(strat.propose(r), np.int64)
            obs = env.step(r, p)
            strat.observe(p, obs.tpd)
            out.append(obs)

    for ref, onl in zip(obs_ref, obs_onl, strict=True):
        assert onl.tpd == ref.tpd                      # bit-for-bit
        assert onl.placement.tolist() == ref.placement.tolist()
        for k in ("loss", "accuracy", "train_time", "agg_time"):
            assert onl.metrics[k] == ref.metrics[k]
        # the degenerate rounds are genuinely synchronous
        assert onl.metrics["overlap"] == 0.0
        assert onl.metrics["staleness_max"] == 0.0
        assert onl.metrics["merged"] == float(h.total_clients)


def test_same_strategy_instance_protocol_both_worlds(emu_setup):
    """One PlacementStrategy class runs unmodified in both environments
    through the identical propose/observe protocol (the API contract)."""
    cfg, h = emu_setup
    for env in (SimulatedEnvironment(
                    h, ClientPool.random(h.total_clients, seed=0)),
                EmulatedEnvironment(_fresh_orchestrator(cfg, h))):
        strat = create_strategy("pso", h, seed=0)
        env.begin()
        for r in range(2):
            p = np.asarray(strat.propose(r), np.int64)
            obs = env.step(r, p)
            assert obs.tpd > 0
            strat.observe(p, obs.tpd)
        assert strat.pso.evaluations == 2
