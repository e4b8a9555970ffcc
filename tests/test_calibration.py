"""The trace-calibration loop (record -> fit -> replay) and the
EvalConfig API redesign.

Pins, in order:

* trace record -> save -> load round trips byte-identically, and
  recording itself is byte-NEUTRAL — default and ``recording='on'``
  runs both reproduce the checked-in simulated golden exactly; emulated
  runs reproduce a same-build twin byte for byte and the golden in
  every field but the trained model's loss/accuracy;
* the least-squares fitter recovers the emulated engine's true
  constants (payload scale 1/EQ6_PAYLOAD_SCALE, per-level link =
  comm_latency, train scale = local_steps) and the fitted model
  strictly beats the analytic baseline on held-out rounds;
* ``batch_predict_cluster_delay`` matches its scalar oracle
  ``_predict_cluster_delay_ref`` (the registered RPL001 pair), and
  un-registering the pair trips the static-analysis gate;
* every environment kind (simulated, sampled, emulated, online) emits
  the SAME ``RoundObservation.timings`` schema, empty when recording
  is off;
* the EvalConfig consolidation: validation, provenance/schema-v4
  stamping, nested CLI overrides, and the deprecation shims for the
  legacy ``mode=``/``shard=`` kwargs.
"""
import json
from pathlib import Path

import numpy as np
import pytest

from repro.calibration import (
    ANALYTIC,
    CalibrationResult,
    TraceArtifact,
    batch_predict_cluster_delay,
    fit_calibration,
    load_calibration,
    record_trace,
    replay,
    validate_trace_dict,
)
from repro.calibration.fit import _predict_cluster_delay_ref
from repro.core.cost_model import CalibratedCostModel, CostModel
from repro.experiments import (
    EvalConfig,
    get_scenario,
    resolve_eval_config,
    run_experiment,
)
from repro.experiments.runner import run_single
from repro.fl.orchestrator import FederatedOrchestrator

GOLDEN = Path(__file__).parent / "golden"

SMOKE = {"model": "mlp-smoke", "local_steps": 1, "batch_size": 16}


@pytest.fixture(scope="module")
def fig4_trace():
    spec = get_scenario("paper-fig4").with_overrides(**SMOKE)
    return record_trace(spec, "pso", seed=0, rounds=4)


# ---------------------------------------------------------------------------
# trace artifact: record / save / load
# ---------------------------------------------------------------------------
def test_trace_save_load_byte_identity(fig4_trace, tmp_path):
    p1 = fig4_trace.save(tmp_path / "a.json")
    reloaded = TraceArtifact.load(p1)
    p2 = reloaded.save(tmp_path / "b.json")
    assert p1.read_bytes() == p2.read_bytes()
    assert reloaded.to_dict() == fig4_trace.to_dict()


def test_trace_schema_validates(fig4_trace):
    d = fig4_trace.to_dict()
    assert validate_trace_dict(d) == []
    bad = dict(d, schema_version=99)
    assert any("schema_version" in e for e in validate_trace_dict(bad))
    bad = dict(d, records=d["records"][:-1])
    assert any("records" in e for e in validate_trace_dict(bad))
    with pytest.raises(ValueError, match="invalid trace"):
        TraceArtifact.from_dict({"schema": "nope"})


def test_trace_records_carry_uniform_rows(fig4_trace):
    for rec in fig4_trace.records:
        assert sorted(rec) == ["agg_time", "levels", "placement",
                               "round", "tpd", "train", "train_time"]
        # levels deepest-first, every cluster row aligned
        levels = [r["level"] for r in rec["levels"]]
        assert levels == sorted(levels, reverse=True)
        for row in rec["levels"]:
            n = len(row["slots"])
            assert n == len(row["hosts"]) == len(row["loads"]) \
                == len(row["n_parts"]) == len(row["delays"])


def test_record_refuses_non_stationary_scenarios():
    with pytest.raises(ValueError, match="events"):
        record_trace("flash-crowd", "pso", rounds=2)
    with pytest.raises(ValueError, match="faults"):
        record_trace("online-faulty", "pso", rounds=2)
    with pytest.raises(ValueError, match="cohort"):
        record_trace("large-100k", "pso", rounds=2)


# ---------------------------------------------------------------------------
# fitter: exact recovery of the engine's constants
# ---------------------------------------------------------------------------
def test_fit_recovers_engine_constants(fig4_trace):
    cal = fit_calibration(fig4_trace, holdout_rounds=1)
    spec = get_scenario("paper-fig4").with_overrides(**SMOKE)
    alpha_true = 1.0 / FederatedOrchestrator.EQ6_PAYLOAD_SCALE
    assert cal.payload_scale == pytest.approx(alpha_true, abs=1e-9)
    assert len(cal.level_link) == fig4_trace.hierarchy["depth"]
    for beta in cal.level_link:
        assert beta == pytest.approx(spec.comm_latency, abs=1e-9)
    assert cal.train_scale == pytest.approx(spec.local_steps, abs=1e-9)
    assert cal.rms_residual < 1e-9
    assert cal.n_rows > 0


def test_fit_holdout_bounds(fig4_trace):
    with pytest.raises(ValueError, match="no fitting rounds"):
        fit_calibration(fig4_trace, holdout_rounds=len(fig4_trace.records))
    with pytest.raises(ValueError, match=">= 0"):
        fit_calibration(fig4_trace, holdout_rounds=-1)


def test_calibration_save_load_round_trip(fig4_trace, tmp_path):
    cal = fit_calibration(fig4_trace)
    path = cal.save(tmp_path / "cal.json")
    assert load_calibration(path) == cal
    with pytest.raises(ValueError, match="not a calibration"):
        CalibrationResult.from_dict({"schema": "nope"})


def test_calibrated_beats_analytic_on_held_out_round(fig4_trace):
    cal = fit_calibration(fig4_trace, holdout_rounds=1)
    held_out = [fig4_trace.records[-1]["round"]]
    err_cal = replay(fig4_trace, cal, rounds=held_out).mean_abs_error
    err_ana = replay(fig4_trace, ANALYTIC, rounds=held_out).mean_abs_error
    assert err_cal < err_ana
    assert err_cal < 1e-6  # linear laws: the fit is essentially exact


def test_replay_reports_every_round_and_level(fig4_trace):
    report = replay(fig4_trace, ANALYTIC)
    assert len(report.rounds) == len(fig4_trace.records)
    for r in report.rounds:
        assert {lvl["level"] for lvl in r["levels"]} == set(
            range(fig4_trace.hierarchy["depth"]))
        assert r["abs_error"] == pytest.approx(
            abs(r["measured"] - r["predicted"]))
    d = report.to_dict()
    assert d["summary"]["n_rounds"] == len(report.rounds)


def test_cost_model_from_trace_predicts_recorded_rounds(fig4_trace):
    cm = CostModel.from_trace(fig4_trace)
    assert isinstance(cm, CalibratedCostModel)
    for rec in fig4_trace.records:
        measured = rec["train_time"] + rec["agg_time"]
        predicted = cm.tpd(np.asarray(rec["placement"]))
        assert predicted == pytest.approx(measured, abs=1e-8)


# ---------------------------------------------------------------------------
# surrogate parity: batch_predict_cluster_delay vs its scalar oracle
# ---------------------------------------------------------------------------
def test_batch_predict_cluster_delay_matches_scalar_ref(fig4_trace):
    cal = fit_calibration(fig4_trace)
    rng = np.random.default_rng(11)
    n = 64
    loads = rng.uniform(1.0, 200.0, n)
    pspeed = rng.uniform(5.0, 15.0, n)
    n_parts = rng.integers(1, 9, n)
    levels = rng.integers(0, len(cal.level_link) + 2, n)  # incl. unseen
    batched = batch_predict_cluster_delay(loads, pspeed, n_parts,
                                          levels, cal)
    for i in range(n):
        ref = _predict_cluster_delay_ref(loads[i], pspeed[i],
                                         int(n_parts[i]),
                                         int(levels[i]), cal)
        assert batched[i] == pytest.approx(ref, rel=1e-12)


def test_rpl001_unregistering_the_surrogate_fails_the_pass():
    """The calibration surrogate is a batch_* def under the scanned
    src/repro/calibration/ prefix: dropping its oracle pair must trip
    the static-analysis gate."""
    from repro.analysis import engine, parity
    from repro.analysis.parity import REGISTRY
    repo = Path(__file__).resolve().parent.parent
    contexts = engine.load_tree(repo)
    full = parity.check(contexts, registry=REGISTRY, root=repo)
    assert not [v for v in full
                if "batch_predict_cluster_delay" in v.message]
    reg = tuple(
        p for p in REGISTRY
        if p.fast != "repro.calibration.fit:batch_predict_cluster_delay")
    violations = parity.check(contexts, registry=reg, root=repo)
    assert any(v.code == "RPL001"
               and "batch_predict_cluster_delay" in v.message
               for v in violations)


# ---------------------------------------------------------------------------
# recording is byte-neutral: default AND recording=on reproduce the
# checked-in pre-PR goldens exactly
# ---------------------------------------------------------------------------
def _fig3_result(**kw):
    spec = get_scenario("paper-fig3").with_overrides(rounds=6)
    return run_experiment(spec, ["pso", "random"], rounds=6,
                          seeds=(0,), progress=False, **kw)


def _fig4_result(**kw):
    spec = get_scenario("paper-fig4").with_overrides(**SMOKE)
    return run_experiment(spec, ["pso"], rounds=2, seeds=(0,),
                          progress=False, **kw)


@pytest.mark.parametrize("eval_config", [
    None,
    EvalConfig(),
    EvalConfig(recording="on"),
], ids=["default", "explicit-default", "recording-on"])
def test_fig3_byte_identical_to_golden(eval_config):
    res = _fig3_result(eval_config=eval_config)
    got = json.dumps(res.to_dict(), indent=1)
    want = (GOLDEN / "recording_off_fig3.json").read_text()
    assert got == want


def _without_training_numerics(d: dict) -> dict:
    """The artifact minus the trained model's loss/accuracy: those
    follow the installed JAX build (its PRNG stream and CPU kernels),
    every other field (placements, TPDs, timings) is build-independent."""
    d = json.loads(json.dumps(d))
    for run in d["runs"]:
        for key in ("metrics", "final_metrics"):
            run[key].pop("loss")
            run[key].pop("accuracy")
    for agg in d["aggregates"].values():
        agg.pop("final_loss")
        agg.pop("final_accuracy")
    return d


@pytest.fixture(scope="module")
def fig4_twin():
    """Recording-off artifact of THIS JAX build — the emulated pin."""
    return json.dumps(_fig4_result(eval_config=EvalConfig()).to_dict(),
                      indent=1)


@pytest.mark.parametrize("eval_config", [
    None,
    EvalConfig(recording="on"),
], ids=["default", "recording-on"])
def test_fig4_byte_identical_to_golden(eval_config, fig4_twin):
    res = _fig4_result(eval_config=eval_config)
    got = json.dumps(res.to_dict(), indent=1)
    assert got == fig4_twin
    want = json.loads((GOLDEN / "recording_off_fig4_mlp_smoke.json")
                      .read_text())
    assert _without_training_numerics(res.to_dict()) == \
        _without_training_numerics(want)


def test_legacy_mode_kwarg_warns_and_stays_byte_identical():
    with pytest.warns(DeprecationWarning, match="eval.mode"):
        res = _fig3_result(mode="sequential")
    got = json.dumps(res.to_dict(), indent=1)
    want = (GOLDEN / "recording_off_fig3.json").read_text()
    assert got == want


# ---------------------------------------------------------------------------
# uniform timings on every environment kind
# ---------------------------------------------------------------------------
_KIND_SPECS = {
    "simulated": lambda: get_scenario("paper-fig3"),
    "sampled": lambda: get_scenario("large-100k").with_overrides(
        pool_size=256, cohort_size=16),
    "emulated": lambda: get_scenario("paper-fig4").with_overrides(**SMOKE),
    "online": lambda: get_scenario("online-fig4").with_overrides(
        model="mlp-smoke"),
}


@pytest.mark.parametrize("kind", sorted(_KIND_SPECS))
def test_every_env_kind_emits_the_uniform_timings_schema(kind):
    spec = _KIND_SPECS[kind]()
    seen = []
    run_single(spec, "pso", seed=0, rounds=2,
               eval_config=EvalConfig(recording="on"),
               on_observation=lambda o: seen.append(o.timings))
    assert len(seen) == 2
    for t in seen:
        assert sorted(t) == ["agg_time", "levels", "train", "train_time"]
        assert sorted(t["train"]) == ["clients", "times"]
        for row in t["levels"]:
            assert sorted(row) == ["delays", "hosts", "level", "loads",
                                   "n_parts", "slots"]


@pytest.mark.parametrize("kind", sorted(_KIND_SPECS))
def test_recording_off_leaves_timings_empty(kind):
    spec = _KIND_SPECS[kind]()
    seen = []
    run_single(spec, "pso", seed=0, rounds=1,
               on_observation=lambda o: seen.append(o.timings))
    assert seen == [{}]


def test_simulated_levels_compose_to_tpd():
    seen = []
    run_single(get_scenario("paper-fig3"), "pso", seed=0, rounds=3,
               eval_config=EvalConfig(recording="on"),
               on_observation=lambda o: seen.append((o.tpd, o.timings)))
    for tpd, t in seen:
        level_sum = sum(max(row["delays"]) for row in t["levels"])
        assert level_sum == pytest.approx(tpd, rel=1e-12)
        assert t["agg_time"] == pytest.approx(tpd, rel=1e-12)


def test_emulated_levels_compose_to_agg_time(fig4_trace):
    for rec in fig4_trace.records:
        level_sum = sum(max(row["delays"]) for row in rec["levels"])
        assert level_sum == pytest.approx(rec["agg_time"], rel=1e-12)


# ---------------------------------------------------------------------------
# EvalConfig: validation, provenance, threading, deprecation shims
# ---------------------------------------------------------------------------
def test_eval_config_validates_fields():
    with pytest.raises(ValueError, match="eval.mode"):
        EvalConfig(mode="warp")
    with pytest.raises(ValueError, match="eval.backend"):
        EvalConfig(backend="cuda")
    with pytest.raises(ValueError, match="eval.shard"):
        EvalConfig(shard="maybe")
    with pytest.raises(ValueError, match="eval.recording"):
        EvalConfig(recording="sometimes")
    with pytest.raises(ValueError, match="calibration"):
        EvalConfig(cost_source="calibrated")  # needs a path
    with pytest.raises(ValueError, match="sequential"):
        EvalConfig(recording="on", mode="batched")


def test_eval_config_provenance_only_semantics_fields():
    assert EvalConfig().provenance() is None
    # execution knobs never reach the artifact
    assert EvalConfig(mode="batched", shard="off").provenance() is None
    assert EvalConfig(recording="on").provenance() is None
    assert EvalConfig(backend="np").provenance() == {"backend": "np"}
    prov = EvalConfig(cost_source="calibrated",
                      calibration="cal.json").provenance()
    assert prov == {"cost_source": "calibrated", "calibration": "cal.json"}


def test_eval_config_with_overrides():
    ec = EvalConfig().with_overrides(mode="batched", backend="np")
    assert (ec.mode, ec.backend) == ("batched", "np")
    assert ec.with_overrides(backend="none").backend is None
    with pytest.raises(TypeError, match="no field"):
        EvalConfig().with_overrides(bogus=1)


def test_resolve_eval_config_shims():
    with pytest.warns(DeprecationWarning, match="eval_config"):
        ec = resolve_eval_config(None, mode="batched")
    assert ec.mode == "batched"
    with pytest.warns(DeprecationWarning):
        same = resolve_eval_config(EvalConfig(mode="batched"),
                                   mode="batched")
    assert same == EvalConfig(mode="batched")
    with pytest.warns(DeprecationWarning):
        with pytest.raises(ValueError, match="conflicting"):
            resolve_eval_config(EvalConfig(mode="sequential"),
                                mode="batched")


def test_default_artifacts_stay_schema_v3_calibrated_stamp_v4(
        fig4_trace, tmp_path):
    res = _fig3_result()
    assert res.stamped_schema_version() == 3
    assert "eval" not in res.to_dict()

    cal = fit_calibration(fig4_trace)
    cal_path = cal.save(tmp_path / "cal.json")
    ec = EvalConfig(cost_source="calibrated", calibration=str(cal_path))
    res4 = _fig3_result(eval_config=ec)
    d = res4.to_dict()
    assert res4.stamped_schema_version() == 4
    assert d["schema_version"] == 4
    assert d["eval"]["cost_source"] == "calibrated"
    from repro.experiments import validate_result_dict
    assert validate_result_dict(d) == []
    # eval section demands the v4 stamp
    bad = dict(d, schema_version=3)
    assert any("eval" in e for e in validate_result_dict(bad))


def test_calibrated_cost_source_threads_into_environment(
        fig4_trace, tmp_path):
    cal_path = fit_calibration(fig4_trace).save(tmp_path / "cal.json")
    ec = EvalConfig(cost_source="calibrated", calibration=str(cal_path))
    env = get_scenario("paper-fig3").make_environment(0, eval_config=ec)
    assert isinstance(env.cost_model, CalibratedCostModel)
    with pytest.raises(ValueError, match="simulated"):
        get_scenario("paper-fig4").with_overrides(**SMOKE) \
            .make_environment(0, eval_config=ec)


def test_recording_on_refuses_batched_runner(tmp_path):
    from repro.experiments.runner import run_batched
    with pytest.raises(ValueError, match="batched"):
        run_batched(get_scenario("paper-fig3"), ["pso"], rounds=2,
                    seeds=(0,), eval_config=EvalConfig(recording="on",
                                                       mode="sequential"))


def test_legacy_make_environment_override_compat():
    """ScenarioSpec subclasses predating the eval_config kwarg still run
    with a default evaluation surface, and fail loudly (not TypeError)
    when the run actually configures one."""
    from repro.experiments.runner import run_single
    from repro.experiments.scenarios import ScenarioSpec

    class LegacySpec(ScenarioSpec):
        def make_environment(self, seed=0):  # old signature
            from repro.experiments.environments import build_environment
            return build_environment(self, seed)

    spec = LegacySpec(name="legacy", kind="simulated", depth=2, width=2,
                      rounds=2)
    run = run_single(spec, "random", seed=0, rounds=2)
    assert len(run.tpds) == 2
    with pytest.raises(ValueError, match="eval_config"):
        run_single(spec, "random", seed=0, rounds=2,
                   eval_config=EvalConfig(cost_source="calibrated",
                                          calibration=ANALYTIC))


def test_cli_nested_eval_overrides(tmp_path, capsys):
    from repro.experiments.cli import main as exp_main
    out = tmp_path / "r.json"
    rc = exp_main(["run", "paper-fig3", "--strategies", "pso",
                   "--rounds", "2", "--set", "eval.mode=sequential",
                   "--out", str(out)])
    assert rc == 0
    d = json.loads(out.read_text())
    assert d["schema_version"] == 3  # execution knob: no eval section
    assert "eval" not in d
    with pytest.raises(SystemExit, match="no field"):
        exp_main(["run", "paper-fig3", "--strategies", "pso",
                  "--rounds", "2", "--set", "eval.bogus=1",
                  "--out", str(out)])


def test_calibration_cli_round_trip(tmp_path):
    from repro.calibration.cli import main as cal_main
    trace_p = tmp_path / "trace.json"
    cal_p = tmp_path / "cal.json"
    assert cal_main(["record", "paper-fig4", "--rounds", "3",
                     "--set", "model=mlp-smoke",
                     "--set", "local_steps=1", "--set", "batch_size=16",
                     "--out", str(trace_p)]) == 0
    assert cal_main(["validate", str(trace_p)]) == 0
    assert cal_main(["fit", str(trace_p), "--holdout", "1",
                     "--out", str(cal_p)]) == 0
    assert cal_main(["replay", str(trace_p),
                     "--calibration", str(cal_p), "--rounds", "2"]) == 0
    assert cal_main(["report", str(trace_p), "--holdout", "1",
                     "--rounds", "2"]) == 0
