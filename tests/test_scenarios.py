"""ScenarioSpec presets, overrides, and event schedules."""

import numpy as np
import pytest

from repro.experiments import (
    ClientChurn,
    LatencyNoise,
    PoolProfile,
    PSpeedDrift,
    ScenarioSpec,
    StragglerSpike,
    get_scenario,
    list_scenarios,
)
from repro.experiments.scenarios import event_from_dict


def test_required_presets_registered():
    names = {s.name for s in list_scenarios()}
    assert {"paper-fig3", "paper-fig4", "drift", "churn", "straggler",
            "latency", "two-tier", "large-256"} <= names


@pytest.mark.parametrize("name", [s.name for s in list_scenarios()])
def test_every_preset_constructs(name):
    spec = get_scenario(name)
    h = spec.make_hierarchy()
    pool = spec.make_pool(seed=0)
    if spec.sampling != "off":
        # sampled presets: the RESIDENT pool is bigger than the tree,
        # which spans only the per-round cohort
        assert len(pool) == spec.pool_size > h.total_clients
        assert h.total_clients == spec.cohort_size
    else:
        assert len(pool) == h.total_clients
    if spec.kind == "simulated":  # emulated build is covered in parity tests
        env = spec.make_environment(seed=0)
        p = np.random.default_rng(0).permutation(
            h.total_clients)[: h.dimensions]
        obs = env.step(0, p)
        assert obs.tpd > 0


def test_fig4_preset_matches_docker_cluster():
    spec = get_scenario("paper-fig4")
    pool = spec.make_pool(seed=123)  # explicit profile ignores the seed
    assert pool.pspeed.tolist() == [4.0, 2.0, 2.0] + [1.0] * 7
    assert pool.memcap.tolist() == [2048.0, 1024.0, 1024.0] + [64.0] * 7
    assert (pool.mdatasize == 30.0).all()
    assert spec.make_hierarchy().total_clients == 10


def test_large_256_preset_scale():
    spec = get_scenario("large-256")
    h = spec.make_hierarchy()
    assert h.total_clients == 256
    assert h.dimensions == 40  # depth-4 / width-3 (eq. 5)


def test_with_overrides_coerces_cli_strings():
    spec = get_scenario("paper-fig3").with_overrides(depth="4", width="5")
    assert spec.depth == 4 and spec.width == 5
    assert get_scenario("paper-fig3").depth == 3  # original untouched
    with pytest.raises(TypeError, match="no field"):
        spec.with_overrides(depht=3)


def test_spec_dict_round_trip():
    spec = get_scenario("straggler")
    d = spec.to_dict()
    back = ScenarioSpec.from_dict(d)
    assert back == spec
    assert back.to_dict() == d
    # an artifact written while the spec still named the round engine's
    # timing and engine reads back as the same spec
    assert ScenarioSpec.from_dict(
        {**d, "timing": "deterministic", "engine": "auto"}) == spec


def test_pool_profile_validation():
    with pytest.raises(ValueError, match="memcap"):
        PoolProfile(kind="explicit")
    with pytest.raises(ValueError, match="kind"):
        PoolProfile(kind="weird")
    prof = PoolProfile(kind="explicit", memcap=(1.0, 2.0),
                       pspeed=(1.0, 2.0))
    with pytest.raises(ValueError, match="clients"):
        prof.make(3, seed=0)


# ---------------------------------------------------------------------------
# event schedules actually mutate the pool
# ---------------------------------------------------------------------------
def _pool(n=16, seed=0):
    from repro.core.hierarchy import ClientPool
    return ClientPool.random(n, seed=seed)


def test_pspeed_drift_reverses_once():
    pool = _pool()
    before = pool.pspeed.copy()
    ev = PSpeedDrift(at_round=5, mode="reverse").fresh()
    rng = np.random.default_rng(0)
    for r in range(5):
        assert ev.on_round(r, pool, rng) is None
    msg = ev.on_round(5, pool, rng)
    assert "drift" in msg
    assert np.array_equal(pool.pspeed, before[::-1])
    assert ev.on_round(6, pool, rng) is None  # one-shot


def test_churn_replaces_fraction():
    pool = _pool(n=20)
    before = pool.pspeed.copy()
    ev = ClientChurn(every=10, fraction=0.25, first_round=1).fresh()
    rng = np.random.default_rng(0)
    assert ev.on_round(0, pool, rng) is None
    msg = ev.on_round(1, pool, rng)
    assert "replaced 5" in msg
    changed = (pool.pspeed != before).sum()
    assert 0 < changed <= 5
    assert (pool.pspeed >= 5).all() and (pool.pspeed < 15).all()
    # silent until the next period
    assert ev.on_round(2, pool, rng) is None
    assert ev.on_round(11, pool, rng) is not None


def test_straggler_spike_slows_then_restores():
    pool = _pool(n=20)
    before = pool.pspeed.copy()
    ev = StragglerSpike(every=15, duration=3, fraction=0.2,
                        slowdown=4.0, first_round=2).fresh()
    rng = np.random.default_rng(0)
    assert ev.on_round(0, pool, rng) is None
    msg = ev.on_round(2, pool, rng)
    assert "straggler" in msg
    slowed = np.where(pool.pspeed < before)[0]
    assert len(slowed) == 4  # 20% of 20
    np.testing.assert_allclose(pool.pspeed[slowed] * 4.0, before[slowed])
    ev.on_round(3, pool, rng)
    ev.on_round(4, pool, rng)
    msg = ev.on_round(5, pool, rng)  # 2 + duration 3 -> recovery
    assert "recovered" in msg
    np.testing.assert_allclose(pool.pspeed, before)


def test_straggler_recovery_skips_concurrently_mutated_clients():
    # composite-schedule safety: if another event (churn, drift) rewrote
    # a slowed client's speed mid-spike, recovery must not clobber it
    pool = _pool(n=20)
    before = pool.pspeed.copy()
    ev = StragglerSpike(every=50, duration=3, fraction=0.2,
                        slowdown=4.0, first_round=0).fresh()
    rng = np.random.default_rng(0)
    ev.on_round(0, pool, rng)
    slowed = sorted(ev._saved)
    victim = slowed[0]
    pool.pspeed[victim] = 99.0  # churn replaced the device mid-spike
    msg = ev.on_round(3, pool, rng)
    assert "recovered" in msg
    assert pool.pspeed[victim] == 99.0  # fresh device untouched
    for c in slowed[1:]:
        assert pool.pspeed[c] == before[c]  # exact restore


def test_latency_noise_transforms_tpd_only():
    pool = _pool()
    before = pool.pspeed.copy()
    ev = LatencyNoise(sigma=0.2).fresh()
    rng = np.random.default_rng(0)
    assert ev.on_round(0, pool, rng) is None
    assert np.array_equal(pool.pspeed, before)
    vals = [ev.transform_tpd(r, 10.0, rng) for r in range(50)]
    assert all(v > 0 for v in vals)
    assert np.std(vals) > 0


def test_event_dict_round_trip():
    for ev in (PSpeedDrift(at_round=9, mode="shuffle"),
               ClientChurn(every=7, fraction=0.5),
               StragglerSpike(every=11, duration=2),
               LatencyNoise(sigma=0.33)):
        back = event_from_dict(ev.to_dict())
        assert type(back) is type(ev)
        assert back.to_dict() == ev.to_dict()


def test_fresh_isolates_event_state():
    tmpl = StragglerSpike(every=5, duration=2, first_round=0)
    pool = _pool()
    rng = np.random.default_rng(0)
    a = tmpl.fresh()
    a.on_round(0, pool, rng)
    assert a._saved and not tmpl._saved  # template untouched
    b = tmpl.fresh()
    assert not b._saved
