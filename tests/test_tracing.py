"""The program's tracer (``repro.utils.tracing``): off outside a profiler
session, per-name totals and self times inside one, its spans in the
profiler's trace by name, every span of the round engine and the TPD
scoring path opened once a round or an iteration, and the benchmark's
readers of them."""
import importlib.util
import sys
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.cost_model import CostModel
from repro.core.hierarchy import ClientPool, Hierarchy
from repro.core.pso import FlagSwapPSO
from repro.data.synthetic import make_federated_dataset
from repro.fl.orchestrator import FederatedOrchestrator
from repro.models import get_model
from repro.utils import tracing

BENCH = Path(__file__).resolve().parents[1] / "bench"

ROUND_SPANS = ("round", "round.inputs", "round.merge", "round.eval")
SEARCH_SPANS = ("search.iteration", "search.score", "tpd.prologue",
                "tpd.transfer", "tpd.wait")


@pytest.fixture(autouse=True)
def clean_tracer():
    tracing.reset()
    yield
    tracing.reset()


def _xplane(logdir: Path) -> bytes:
    paths = list(logdir.rglob("*.xplane.pb"))
    assert len(paths) == 1, paths
    return paths[0].read_bytes()


def _host_events(xspace: bytes, name: str) -> list:
    """(stats, duration_ns) of every host event called ``name``."""
    data = jax.profiler.ProfileData.from_serialized_xspace(xspace)
    return [(dict(e.stats), e.duration_ns) for plane in data.planes
            if plane.name.startswith("/host:") for line in plane.lines
            for e in line.events if e.name == name]


def test_off_outside_a_profiler_session():
    assert not jax.profiler.TraceAnnotation.is_enabled()
    span = tracing.span("round", round=3)
    assert span is tracing.OFF
    assert tracing.span("round.inputs") is span
    with span:
        with tracing.span("round.wait"):
            tracing.count("round.local_calls", 2)
    assert tracing.snapshot() == {"seconds": {}, "self_seconds": {},
                                  "count": {}, "counters": {}}


def test_nested_spans_and_counters_under_the_profiler(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        with tracing.span("outer", round=0):
            time.sleep(0.02)
            for s in (0.03, 0.01):
                with tracing.span("inner"):
                    time.sleep(s)
            tracing.count("calls")
            tracing.count("calls", 2)
    snap = tracing.snapshot()
    assert snap["count"] == {"outer": 1, "inner": 2}
    assert snap["counters"] == {"calls": 3}
    sec, own = snap["seconds"], snap["self_seconds"]
    assert sec["inner"] >= 0.04
    assert own["inner"] == sec["inner"]          # no children
    assert sec["outer"] >= sec["inner"] + 0.02
    assert own["outer"] == pytest.approx(sec["outer"] - sec["inner"],
                                         abs=1e-9)
    assert own["outer"] >= 0.02
    tracing.reset()
    assert tracing.snapshot() == {"seconds": {}, "self_seconds": {},
                                  "count": {}, "counters": {}}


def test_spans_land_in_the_xplane_by_name(tmp_path):
    sys.path.insert(0, str(BENCH))
    try:
        from harness import trace
    finally:
        sys.path.remove(str(BENCH))
    with jax.profiler.trace(str(tmp_path)):
        with tracing.span("window"):
            for r in range(3):
                with tracing.span("round", round=r):
                    with tracing.span("round.inputs"):
                        time.sleep(0.002)
                    jax.numpy.ones(8).block_until_ready()
    xspace = _xplane(tmp_path)
    ev = trace.condense(xspace, set(tracing.snapshot()["count"]))
    names = [n for n, _, _ in ev["host"]]
    assert sorted(names) == sorted(["window"] + ["round"] * 3
                                   + ["round.inputs"] * 3)
    spans = {n: (s, s + d) for n, s, d in ev["host"] if n != "round"}
    rounds = sorted((s, s + d) for n, s, d in ev["host"] if n == "round")
    lo, hi = spans["window"]
    assert all(lo <= a <= b <= hi for a, b in rounds)
    # the request span carries the identifier its children share
    assert sorted(st["round"] for st, _ in _host_events(xspace, "round")) \
        == [0, 1, 2]
    # the trace's durations and the tracer's totals agree
    total = sum(d for _, d in _host_events(xspace, "round.inputs")) / 1e9
    assert total == pytest.approx(tracing.snapshot()["seconds"]
                                  ["round.inputs"], rel=0.2, abs=2e-4)


@pytest.fixture(scope="module")
def emulated():
    """Two identical 4-client orchestrators on the paper MLP, one
    client's shard cut under the batch size: two batch-shape buckets."""
    cfg = get_config("paper-mlp-1m8")
    model = get_model(cfg)
    h = Hierarchy(depth=2, width=1, trainers_per_leaf=2, n_clients=4)

    def build():
        data = make_federated_dataset(cfg, h.total_clients, seed=0)
        data.partitions[0] = data.partitions[0][:5]
        orch = FederatedOrchestrator(
            model, h, ClientPool.random(h.total_clients, seed=0), data,
            local_steps=2, batch_size=16, seed=0)
        orch.warmup()
        return orch, data

    (orch, data), (twin, _) = build(), build()
    buckets = len({min(16, len(p)) for p in data.partitions})
    assert buckets == 2
    return orch, twin, buckets, h


def test_round_opens_every_round_span_once_a_round(emulated, tmp_path):
    orch, twin, buckets, h = emulated
    placement = np.arange(h.dimensions)
    with jax.profiler.trace(str(tmp_path)):
        traced = [orch.run_round(r, placement) for r in range(2)]
    snap = tracing.snapshot()
    for name in ROUND_SPANS:
        assert snap["count"][name] == 2, name
    # the local step's wait and the evaluation's readback, each round
    assert snap["count"]["round.wait"] == 4
    assert snap["counters"] == {"round.local_calls": 2 * buckets,
                                "round.indexed_calls": 2 * buckets}
    own, sec = snap["self_seconds"], snap["seconds"]
    # the evaluation's readback is the eval span's child; the local
    # step's wait is the round's
    eval_wait = sec["round.eval"] - own["round.eval"]
    assert 0 < eval_wait < sec["round.wait"]
    children = sec["round.inputs"] + sec["round.merge"] + sec["round.eval"] \
        + sec["round.wait"] - eval_wait
    assert own["round"] == pytest.approx(sec["round"] - children, abs=1e-9)
    assert own["round"] > 0
    # the spans do not change what the round computes
    plain = [twin.run_round(r, placement) for r in range(2)]
    assert not tracing.snapshot()["count"].keys() - set(snap["count"])
    for a, b in zip(traced, plain, strict=True):
        assert (a.tpd, a.loss, a.accuracy) == (b.tpd, b.loss, b.accuracy)
    for x, y in zip(jax.tree.leaves(orch.params), jax.tree.leaves(
            twin.params), strict=True):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_search_opens_every_scoring_span_once_an_iteration(tmp_path):
    h = Hierarchy(depth=2, width=2, trainers_per_leaf=2, n_clients=12)
    pool = ClientPool.random(h.total_clients, seed=1)
    cm = CostModel(h, pool)
    cm.set_default_backend("interpret")

    def swarm():
        return FlagSwapPSO(h.dimensions, h.total_clients, n_particles=6,
                           seed=2)

    cm.batch_fitness(swarm().placements())       # compile outside
    pso = swarm()
    with jax.profiler.trace(str(tmp_path)):
        best = pso.run(cm.fitness, iterations=2,
                       batch_fitness_fn=cm.batch_fitness)
    snap = tracing.snapshot()
    assert snap["count"] == dict.fromkeys(SEARCH_SPANS, 2)
    # every scored batch built its leaf loads on the device
    assert snap["counters"] == {"tpd.calls": 2, "tpd.device_leaf_calls": 2}
    own, sec = snap["self_seconds"], snap["seconds"]
    scoring = sec["tpd.prologue"] + sec["tpd.transfer"] + sec["tpd.wait"]
    assert scoring <= sec["search.score"]
    assert own["search.iteration"] == pytest.approx(
        sec["search.iteration"] - sec["search.score"], abs=1e-9)
    its = sorted(st["iteration"] for st, _ in _host_events(
        _xplane(tmp_path), "search.iteration"))
    assert its == [0, 1]
    # the spans do not change the search
    plain = swarm()
    np.testing.assert_array_equal(
        best, plain.run(cm.fitness, iterations=2,
                        batch_fitness_fn=cm.batch_fitness))
    assert pso.history.best == plain.history.best


# ----------------------------------------------------------------------
# the benchmark's readers of the tracer
# ----------------------------------------------------------------------
SNAPSHOT = {
    "seconds": {"round": 2.0, "round.inputs": 0.8, "round.merge": 0.12,
                "round.eval": 0.2, "round.wait": 0.4, "search.iteration": 3.0,
                "search.score": 2.0, "tpd.prologue": 0.6,
                "tpd.transfer": 1.0, "tpd.wait": 0.2},
    "self_seconds": {"round": 0.52, "round.inputs": 0.8, "round.merge": 0.12,
                     "round.eval": 0.08, "round.wait": 0.4,
                     "search.iteration": 1.0, "search.score": 0.2,
                     "tpd.prologue": 0.6, "tpd.transfer": 1.0,
                     "tpd.wait": 0.2},
    "count": {},
    "counters": {"round.local_calls": 12, "round.indexed_calls": 9,
                 "tpd.calls": 10, "tpd.device_leaf_calls": 8},
}
UNITS = 4
READERS = {
    "round.host_ms": 0.52 / UNITS * 1e3,
    "round.input_ms": 0.8 / UNITS * 1e3,
    "round.merge_host_ms": 0.12 / UNITS * 1e3,
    "round.eval_host_ms": 0.08 / UNITS * 1e3,
    "round.wait_ms": 0.4 / UNITS * 1e3,
    "round.local_calls": 12 / UNITS,
    "round.indexed_share": 9 / 12,
    "search.update_ms": 1.0 / UNITS * 1e3,
    "search.prologue_ms": 0.6 / UNITS * 1e3,
    "search.transfer_ms": 1.0 / UNITS * 1e3,
    "search.wait_ms": 0.2 / UNITS * 1e3,
    "search.device_leaf_share": 8 / 10,
}


def _reader(name):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.mark.parametrize("name", sorted(READERS))
def test_metric_reads_the_snapshot(name, monkeypatch):
    monkeypatch.setattr(tracing, "snapshot", lambda: SNAPSHOT)
    assert _reader(name)({"stats": {"units": UNITS}}) == \
        pytest.approx(READERS[name], rel=1e-12)


@pytest.mark.parametrize("program", ["span never opened", "no tracer"])
@pytest.mark.parametrize("name", sorted(READERS))
def test_metric_is_none_without_its_span(name, program, monkeypatch):
    if program == "no tracer":
        # a program that predates the tracer: the import fails
        monkeypatch.setitem(sys.modules, "repro.utils.tracing", None)
        monkeypatch.delattr("repro.utils.tracing", raising=False)
    assert _reader(name)({"stats": {"units": UNITS}}) is None


def test_indexed_share_is_none_without_the_indexed_counter(monkeypatch):
    """A program that counts ``local_all`` dispatches but has no indexed
    input path reports no share."""
    snap = dict(SNAPSHOT, counters={"round.local_calls": 12})
    monkeypatch.setattr(tracing, "snapshot", lambda: snap)
    assert _reader("round.indexed_share")({"stats": {"units": UNITS}}) \
        is None


def test_device_leaf_share_is_zero_when_scoring_stays_on_the_host(
        monkeypatch):
    """A program that counts its scoring calls, none of which built the
    leaf loads on the device (the numpy path), reads a share of 0."""
    snap = dict(SNAPSHOT, counters={"tpd.calls": 7})
    monkeypatch.setattr(tracing, "snapshot", lambda: snap)
    assert _reader("search.device_leaf_share")(
        {"stats": {"units": UNITS}}) == 0.0
