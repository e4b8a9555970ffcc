"""Swarm-scale parity suite: every vectorized fast path in the scale
engine pinned against its sequential reference.

* ``batch_tpd`` (numpy, jit, Pallas-interpret) vs the scalar
  ``CostModel.tpd`` / ``TwoTierCostModel`` at >= 1k clients with
  heterogeneous mdatasize + memory penalty;
* the EXACT float64 path (``tpd_fast`` / ``PooledTPDEvaluator``)
  bit-identical to the scalar model, including after in-place pool
  mutation mid-run (version-counter invalidation);
* vectorized ``FlagSwapPSO.run`` bit-for-bit against the per-particle
  ``_run_reference`` oracle over 50 iterations;
* the batched lockstep sweep runner bit-identical to the sequential
  runner, events and all.
"""
import numpy as np
import pytest

from repro.core.cost_model import CostModel, PooledTPDEvaluator, TwoTierCostModel
from repro.core.hierarchy import ClientPool, Hierarchy
from repro.core.pso import FlagSwapPSO
from repro.experiments import get_scenario, run_experiment


def _scale_setup(n_clients=1024, depth=5, width=3, seed=0, hetero=True,
                 penalty=3.0):
    h = Hierarchy(depth=depth, width=width, trainers_per_leaf=2,
                  n_clients=n_clients)
    pool = ClientPool.random(n_clients, seed=seed)
    if hetero:
        rng = np.random.default_rng(seed + 100)
        pool.mdatasize = rng.uniform(1.0, 40.0, n_clients)
    return h, pool, CostModel(h, pool, memory_penalty=penalty)


def _placements(h, n, seed=0):
    rng = np.random.default_rng(seed)
    return np.stack([rng.permutation(h.total_clients)[: h.dimensions]
                     for _ in range(n)]).astype(np.int32)


# ---------------------------------------------------------------------------
# batch_tpd backends vs the scalar model at scale
# ---------------------------------------------------------------------------
def test_batch_tpd_backends_at_1k_hetero_with_penalty():
    h, pool, cm = _scale_setup()
    ps = _placements(h, 4)
    scalar = np.array([cm.tpd(p) for p in ps])
    for backend in ("np", "jit", "pallas"):
        got = np.asarray(cm.batch_tpd(ps, backend=backend))
        # f32 accumulation: documented tolerance vs the f64 scalar model
        np.testing.assert_allclose(got, scalar, rtol=2e-5,
                                   err_msg=backend)


def test_tpd_fast_exact_at_1k():
    """The float64 single-placement fast path (what env.step runs) is
    bit-identical to the scalar model — atol=0, no tolerance."""
    for hetero in (False, True):
        h, pool, cm = _scale_setup(hetero=hetero)
        for p in _placements(h, 3):
            assert cm.tpd_fast(p) == cm.tpd(p)


def test_two_tier_batch_tpd_at_1k():
    h = Hierarchy(depth=5, width=3, trainers_per_leaf=2, n_clients=1024)
    rng = np.random.default_rng(0)
    pool = ClientPool.random(1024, seed=0)
    pool.mdatasize = rng.uniform(1.0, 40.0, 1024)
    tt = TwoTierCostModel(h, pool, memory_penalty=2.0,
                          pod_of=rng.integers(0, 8, 1024))
    ps = _placements(h, 3)
    scalar = np.array([tt.tpd(p) for p in ps])
    np.testing.assert_allclose(np.asarray(tt.batch_tpd(ps, backend="np")),
                               scalar, rtol=2e-5)
    for p in ps:  # exact f64 path covers the pod edge costs too
        assert tt.tpd_fast(p) == tt.tpd(p)
    # the Pallas kernel does NOT model pod edges: explicit request fails
    with pytest.raises(ValueError, match="pod"):
        tt.batch_tpd(ps, backend="pallas")


def test_exact_path_tracks_mid_run_pool_mutation():
    """In-place pool mutation mid-run: the version counter invalidates
    every cached evaluator tier (np/f64/pooled)."""
    h, pool, cm = _scale_setup(n_clients=256, depth=4, width=3)
    ps = _placements(h, 3)
    before = [cm.tpd_fast(p) for p in ps]
    rng = np.random.default_rng(9)
    pool.pspeed[:] = rng.uniform(5, 15, len(pool))
    pool.touch()
    for p, old in zip(ps, before, strict=True):
        now = cm.tpd_fast(p)
        assert now == cm.tpd(p)
        assert now != old


def test_pooled_evaluator_bit_identical_rows():
    h = Hierarchy(depth=5, width=3, trainers_per_leaf=2, n_clients=1024)
    pools = [ClientPool.random(1024, seed=s) for s in range(3)]
    rng = np.random.default_rng(3)
    for p in pools:
        p.mdatasize = rng.uniform(1.0, 40.0, 1024)
    models = [CostModel(h, p, memory_penalty=1.5) for p in pools]
    ev = PooledTPDEvaluator(models)
    ps = _placements(h, 3, seed=1)
    got = ev.tpds(ps)
    for s in range(3):
        assert got[s] == models[s].tpd_fast(ps[s])
        assert got[s] == models[s].tpd(ps[s])
    # pool_idx row mapping + mid-run mutation of ONE pool
    pools[1].pspeed[:] = pools[1].pspeed * 3.0
    pools[1].touch()
    got2 = ev.tpds(np.concatenate([ps, ps]),
                   pool_idx=np.array([0, 1, 2, 0, 1, 2]))
    for s in range(3):
        want = models[s].tpd_fast(ps[s])
        assert got2[s] == want and got2[s + 3] == want
    assert got2[1] != got[1]


def test_cross_pod_edges_matches_scalar_reference():
    """Vectorized locality metric == the retained double-loop oracle,
    valid placements and duplicate-id placements alike."""
    h = Hierarchy(depth=4, width=3, trainers_per_leaf=2, n_clients=120)
    rng = np.random.default_rng(2)
    pool = ClientPool.random(120, seed=2)
    tt = TwoTierCostModel(h, pool, pod_of=rng.integers(0, 5, 120))
    for _ in range(25):
        p = rng.permutation(120)[: h.dimensions]
        assert tt.cross_pod_edges(p) == tt._cross_pod_edges_ref(p)
    dup = rng.permutation(120)[: h.dimensions]
    dup[-1] = dup[0]
    assert tt.cross_pod_edges(dup) == tt._cross_pod_edges_ref(dup)
    # pod-less model: zero cross edges, trainer-aware total
    base = TwoTierCostModel(h, pool, pod_of=None)
    p = rng.permutation(120)[: h.dimensions]
    assert base.cross_pod_edges(p) == base._cross_pod_edges_ref(p)


def test_uniform_fast_path_handles_duplicate_ids():
    """Placements with repeated client ids are legal inputs to the
    scalar model (one fewer trainer); the uniform-payload fast path
    must fall back to the general machinery for them, not silently
    misprice the leaves."""
    h = Hierarchy(depth=3, width=2, trainers_per_leaf=2, n_clients=20)
    pool = ClientPool.random(20, seed=0)      # uniform mdatasize
    cm = CostModel(h, pool)
    dup = np.arange(h.dimensions)
    dup[-1] = dup[0]                          # duplicate id
    assert cm.tpd_fast(dup) == cm.tpd(dup)
    mixed = np.stack([dup, np.arange(h.dimensions) + 5])
    np.testing.assert_allclose(
        np.asarray(cm.batch_tpd(mixed, backend="np")),
        [cm.tpd(p) for p in mixed], rtol=1e-5)


def test_placements_returns_a_copy():
    pso = FlagSwapPSO(7, 16, n_particles=4, seed=0)
    held = pso.placements()
    held[:] = -1                              # caller-side mutation
    assert pso.placements().min() >= 0        # cache uncorrupted
    pso.tell(-1.0)
    again = pso.placements()
    assert again is not held and again.min() >= 0


def test_batched_mode_rejects_custom_step_environments():
    from repro.experiments import SimulatedEnvironment, run_batched
    from repro.experiments.scenarios import ScenarioSpec

    class MetricEnv(SimulatedEnvironment):
        def step(self, round_idx, placement):
            obs = super().step(round_idx, placement)
            obs.metrics["extra"] = 1.0
            return obs

    class CustomSpec(ScenarioSpec):
        def make_environment(self, seed=0):
            h = self.make_hierarchy()
            return MetricEnv(h, self.make_pool(seed))

    spec = CustomSpec(name="custom", kind="simulated", depth=2, width=2)
    with pytest.raises(ValueError, match="overrides"):
        run_batched(spec, [("pso", None)], seeds=(0,), rounds=2)
    # sequential mode still records the custom metrics
    res = run_experiment(spec, ["pso"], rounds=2, seeds=(0,),
                         progress=False, mode="sequential")
    assert res.runs[0].metrics["extra"] == [1.0, 1.0]


def test_pooled_evaluator_rejects_mismatched_models():
    h = Hierarchy(depth=3, width=2, trainers_per_leaf=2)
    h2 = Hierarchy(depth=3, width=2, trainers_per_leaf=3)
    pool = ClientPool.random(h.total_clients, seed=0)
    pool2 = ClientPool.random(h2.total_clients, seed=0)
    with pytest.raises(ValueError, match="hierarchy"):
        PooledTPDEvaluator([CostModel(h, pool), CostModel(h2, pool2)])
    with pytest.raises(ValueError, match="penalty"):
        PooledTPDEvaluator([CostModel(h, pool),
                            CostModel(h, pool, memory_penalty=2.0)])


# ---------------------------------------------------------------------------
# Pallas kernel vs its jnp oracle
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("case,C,L,P", [
    ("heterogeneous", 200, 27, 7),
    ("duplicate ids", 200, 27, 7),
    ("C not a multiple of L", 1001, 64, 5),
    ("P not a multiple of 128", 512, 32, 130),
    ("P = 1", 53, 16, 1),
])
def test_device_leaf_loads_match_host_bincount(case, C, L, P):
    """``kernels.tpd.leaf_loads`` (placed mask, binary-shift compaction,
    fold by leaf) against its host bincount oracle ``leaf_loads_ref``."""
    import jax.numpy as jnp
    from repro.kernels.ref import leaf_loads_ref
    from repro.kernels.tpd import leaf_loads

    rng = np.random.default_rng(C + P)
    mds = rng.uniform(1.0, 40.0, C).astype(np.float32)
    D = min(C // 3, 3 * L)
    ps = np.stack([rng.permutation(C)[:D] for _ in range(P)]
                  ).astype(np.int32)
    if case == "duplicate ids":
        ps[0, D // 2:] = ps[0, 0]        # half of row 0 repeats one id
        ps[3, :] = ps[3, 1]              # row 3 places a single client
    want = leaf_loads_ref(ps, mds, L)
    got = np.asarray(leaf_loads(jnp.asarray(ps), jnp.asarray(mds), L))
    assert got.shape == (P, L) and got.dtype == np.float32
    np.testing.assert_allclose(got, want, rtol=1e-6)
    # every unplaced client's payload lands in exactly one leaf
    for row, loads in zip(ps, got, strict=True):
        unplaced = np.setdiff1d(np.arange(C), row)
        assert loads.sum() == pytest.approx(mds[unplaced].sum(), rel=1e-6)


def test_pallas_tpd_kernel_matches_oracle_exactly():
    import jax.numpy as jnp
    from repro.kernels.ref import leaf_loads_ref, tpd_ref
    from repro.kernels.tpd import batch_tpd_pallas

    h = Hierarchy(depth=4, width=3, trainers_per_leaf=2, n_clients=200)
    rng = np.random.default_rng(0)
    pool = ClientPool.random(200, seed=0)
    pool.mdatasize = rng.uniform(1.0, 40.0, 200)
    cm = CostModel(h, pool, memory_penalty=2.5)
    P, C, L = 7, 200, h.n_leaves
    ps = _placements(h, P, seed=2)
    attrs = cm._attr_stack(np.float32)
    leaf_load = leaf_loads_ref(ps, attrs[0], L)
    shape = dict(depth=h.depth, width=h.width, penalty=2.5)
    kern = batch_tpd_pallas(jnp.asarray(ps), jnp.asarray(attrs),
                            jnp.asarray(leaf_load), interpret=True, **shape)
    ref = tpd_ref(jnp.asarray(ps), jnp.asarray(attrs),
                  jnp.asarray(leaf_load), **shape)
    assert jnp.array_equal(kern, ref)  # atol=0 vs the jnp oracle
    scalar = np.array([cm.tpd(p) for p in ps])
    np.testing.assert_allclose(np.asarray(kern), scalar, rtol=2e-5)


@pytest.mark.parametrize("depth,width,n_clients,P,penalty", [
    (4, 3, 200, 300, 1.5),   # three 128-particle lane tiles + pad
    (3, 4, 53, 20, 0.0),     # the paper-fig3 tree
    (3, 1, 12, 5, 2.0),      # width 1: a chain, stride-1 kid rows
    (1, 3, 6, 9, 1.0),       # depth 1: the root is the only level
])
def test_pallas_tpd_lane_tiles_match_tpd_ref(depth, width, n_clients, P,
                                             penalty):
    """The kernel over tree shapes and particle counts that cross the
    128-particle lane tile: pinned exactly against the jnp oracle and
    within f32 tolerance against the float64 scalar model."""
    import jax.numpy as jnp
    from repro.kernels.ref import leaf_loads_ref, tpd_ref
    from repro.kernels.tpd import batch_tpd_pallas

    h = Hierarchy(depth=depth, width=width, trainers_per_leaf=2,
                  n_clients=n_clients)
    rng = np.random.default_rng(3)
    pool = ClientPool.random(n_clients, seed=3)
    pool.mdatasize = rng.uniform(1.0, 40.0, n_clients)
    cm = CostModel(h, pool, memory_penalty=penalty)
    ps = _placements(h, P, seed=4)
    attrs = cm._attr_stack(np.float32)
    leaf_load = leaf_loads_ref(ps, attrs[0], h.n_leaves)
    shape = dict(depth=depth, width=width, penalty=penalty)
    ref = tpd_ref(jnp.asarray(ps), jnp.asarray(attrs),
                  jnp.asarray(leaf_load), **shape)
    kern = batch_tpd_pallas(jnp.asarray(ps), jnp.asarray(attrs),
                            jnp.asarray(leaf_load), interpret=True, **shape)
    assert jnp.array_equal(kern, ref)
    scalar = np.array([cm.tpd(p) for p in ps])
    np.testing.assert_allclose(np.asarray(kern), scalar, rtol=2e-5)


# ---------------------------------------------------------------------------
# vectorized PSO vs the reference loop
# ---------------------------------------------------------------------------
def test_vectorized_pso_run_bit_identical_50_iters():
    h = Hierarchy(depth=3, width=4, trainers_per_leaf=2, n_clients=80)
    pool = ClientPool.random(80, seed=5)
    cm = CostModel(h, pool)
    vec = FlagSwapPSO(h.dimensions, 80, n_particles=10, seed=11)
    ref = FlagSwapPSO(h.dimensions, 80, n_particles=10, seed=11)
    best_v = vec.run(cm.fitness, iterations=50,
                     batch_fitness_fn=cm.batch_fitness)
    best_r = ref._run_reference(cm.fitness, iterations=50,
                                batch_fitness_fn=cm.batch_fitness)
    assert np.array_equal(best_v, best_r)
    assert np.array_equal(vec.x, ref.x)
    assert np.array_equal(vec.v, ref.v)
    assert np.array_equal(vec.pbest_x, ref.pbest_x)
    assert np.array_equal(vec.pbest_f, ref.pbest_f)
    assert np.array_equal(vec.gbest_x, ref.gbest_x)
    assert vec.gbest_f == ref.gbest_f
    assert vec.history.best == ref.history.best
    assert vec.history.worst == ref.history.worst
    assert vec.history.mean == ref.history.mean
    assert all(np.array_equal(a, b) for a, b in
               zip(vec.history.per_particle, ref.history.per_particle,
                   strict=True))


def test_vectorized_pso_scalar_fitness_route():
    def f(p):
        return -float(np.sum(np.asarray(p) * np.arange(len(p))))
    vec = FlagSwapPSO(9, 24, n_particles=6, seed=3)
    ref = FlagSwapPSO(9, 24, n_particles=6, seed=3)
    assert np.array_equal(vec.run(f, 30), ref._run_reference(f, 30))
    assert np.array_equal(vec.x, ref.x)


def test_dedup_fix_exhaustive_small_case():
    """The array-based increment rule == the sequential loop over EVERY
    length-4 row on 5 clients (625 cases, cascades and wraps included)."""
    import itertools
    pso = FlagSwapPSO(4, 5, n_particles=2, seed=0)
    for row in itertools.product(range(5), repeat=4):
        got = pso._dedup_fix(np.array([row], np.int64))[0]
        want = pso._dedup_ints(np.array(row, np.int64))
        assert np.array_equal(got, want), row


def test_dedup_batch_matches_reference_rule():
    pso = FlagSwapPSO(9, 12, n_particles=4, seed=0)
    rng = np.random.default_rng(5)
    pos = rng.uniform(0, 24, (100, 9))       # heavy collisions (mod 12)
    got = pso._dedup_batch(pos.copy())
    want = np.stack([
        pso._dedup_ints(np.floor(r).astype(np.int64) % 12) for r in pos])
    assert np.array_equal(got, want)
    # memoized single-row path agrees and never aliases its cache
    row = pso._dedup(pos[0])
    row[:] = -1
    assert pso._dedup(pos[0]).min() >= 0
    assert np.array_equal(pso._dedup(pos[0]), want[0])


def test_swarm_history_record_flag():
    pso = FlagSwapPSO(7, 16, n_particles=4, seed=0,
                      record_per_particle=False)
    pso.run(lambda p: -1.0, iterations=5)
    assert pso.history.per_particle == []
    assert len(pso.history.best) == 5
    assert pso.history.as_dict()["per_particle"] == []
    # flag reaches the strategy layer through the typed config
    from repro.core.registry import create_strategy
    h = Hierarchy(depth=2, width=2, trainers_per_leaf=1)
    strat = create_strategy("pso", h, record_per_particle=False)
    assert strat.pso.history.record_per_particle is False


# ---------------------------------------------------------------------------
# batched lockstep runner vs the sequential runner
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("scenario,strategies,rounds", [
    ("churn", ["pso", "random"], 20),
    ("straggler", ["pso-adaptive", "uniform"], 25),
    ("latency", ["pso", "sa"], 15),
    ("two-tier", ["pso", "cem"], 15),
    ("large-256", ["pso", "random", "greedy"], 8),
])
def test_batched_runner_bit_identical(scenario, strategies, rounds):
    a = run_experiment(scenario, strategies, rounds=rounds, seeds=(0, 1),
                       progress=False, mode="sequential")
    b = run_experiment(scenario, strategies, rounds=rounds, seeds=(0, 1),
                       progress=False, mode="batched")
    assert [r.to_dict() for r in a.runs] == [r.to_dict() for r in b.runs]


def test_batched_runner_rejects_emulated():
    with pytest.raises(ValueError, match="simulated-only"):
        run_experiment("paper-fig4", ["pso"], rounds=2, seeds=(0,),
                       progress=False, mode="batched")


def test_scale_presets_registered_and_runnable():
    for name, clients, slots in (("large-1k", 1024, 364),
                                 ("large-4k", 4096, 341),
                                 ("large-10k", 10000, 1365)):
        spec = get_scenario(name)
        h = spec.make_hierarchy()
        assert h.total_clients == clients
        assert h.dimensions == slots
    res = run_experiment("large-1k", ["pso"], rounds=3, seeds=(0,),
                         progress=False)
    assert len(res.runs[0].tpds) == 3
    assert all(t > 0 for t in res.runs[0].tpds)


# ---------------------------------------------------------------------------
# interpret escape hatch (kernel body exercised off-TPU)
# ---------------------------------------------------------------------------
def test_batch_tpd_interpret_escape_hatch():
    """backend='interpret' forces the Pallas INTERPRETER on any host:
    the kernel body runs in CI without an accelerator, pinned against
    the scalar model; on non-accelerator backends 'pallas' falls back
    to the same interpreted build (identical outputs). The leaf loads
    are built in the same program; row 2 repeats an id."""
    h, pool, cm = _scale_setup(n_clients=256, depth=4, width=3)
    ps = _placements(h, 5)
    ps[2, 5:] = ps[2, 4]
    scalar = np.array([cm.tpd(p) for p in ps])
    got = np.asarray(cm.batch_tpd(ps, backend="interpret"))
    np.testing.assert_allclose(got, scalar, rtol=2e-5)
    np.testing.assert_array_equal(
        got, np.asarray(cm.batch_tpd(ps, backend="pallas")))
    with pytest.raises(ValueError, match="backend"):
        cm.batch_tpd(ps, backend="bogus")


# ---------------------------------------------------------------------------
# device-sharded pooled sweep (shard_rows segment-sum merge)
# ---------------------------------------------------------------------------
def test_pooled_tpds_sharded_single_device():
    """On 1 device, shard='auto'/'off' IS the numpy path (bit-identical
    by construction); the forced sharded build (tpds_sharded) must
    agree with the sequential tpd_fast oracle to f64 round-off."""
    h = Hierarchy(depth=3, width=2, trainers_per_leaf=2, n_clients=24)
    models = [CostModel(h, ClientPool.random(24, seed=s),
                        memory_penalty=0.5) for s in range(6)]
    ps = _placements(h, 6, seed=9)
    auto = PooledTPDEvaluator(models, shard="auto").tpds(ps)
    off = PooledTPDEvaluator(models, shard="off").tpds(ps)
    np.testing.assert_array_equal(auto, off)  # same code path: exact
    oracle = np.array([m.tpd_fast(p) for m, p in zip(models, ps)])
    np.testing.assert_array_equal(off, oracle)
    sharded = PooledTPDEvaluator(models).tpds_sharded(ps, ndev=1)
    np.testing.assert_allclose(sharded, oracle, rtol=1e-12)


def test_pooled_tpds_sharded_multi_device_vs_sequential_oracle():
    """8 forged CPU devices in a subprocess: the shard_map row shards +
    segment-sum merge (fl.distributed.shard_rows) vs the sequential
    tpd_fast oracle, including a non-divisible row count (pad path)
    and explicit pool_idx routing."""
    import json as _json
    import os
    import subprocess
    import sys
    import textwrap

    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import json
        import numpy as np
        import jax
        from repro.core.cost_model import CostModel, PooledTPDEvaluator
        from repro.core.hierarchy import ClientPool, Hierarchy

        assert jax.local_device_count() == 8
        h = Hierarchy(3, 2, 2, n_clients=24)
        models = [CostModel(h, ClientPool.random(24, seed=s),
                            memory_penalty=0.3) for s in range(5)]
        rng = np.random.default_rng(0)
        ps = np.stack([rng.permutation(24)[: h.dimensions]
                       for _ in range(21)]).astype(np.int32)  # pad path
        idx = rng.integers(0, 5, size=21)
        ev = PooledTPDEvaluator(models, shard="auto")
        got = ev.tpds(ps, pool_idx=idx)      # 21 rows >= 8 -> sharded
        oracle = np.array([models[i].tpd_fast(p)
                           for i, p in zip(idx, ps)])
        print(json.dumps({
            "err": float(np.abs(got - oracle).max()),
            "scale": float(np.abs(oracle).max()),
        }))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..",
                                     "src")
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    res = _json.loads(out.stdout.strip().splitlines()[-1])
    assert res["err"] <= 1e-12 * max(res["scale"], 1.0), res
