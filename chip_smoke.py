"""Smoke run of the system's main path on a TPU, through its public API.

    python3 chip_smoke.py             # one chip: phases A and B
    python3 chip_smoke.py --chips 4   # four chips: the sharded pooled phase

Phase A, the emulated FL round at full width: ``run_experiment`` on
``paper-fig4`` (10 heterogeneous clients training the 1.79M-parameter
``paper-mlp-1m8`` on the batched round engine, deterministic eq. 6
timing) for the ``pso`` and ``random`` strategies, 3 rounds. The same
sweep is rerun on the host CPU backend in this process as the
reference: the per-round TPD series must match exactly, losses must be
finite, and each run's final global parameters must agree with the
CPU's within ``PARAM_UPDATE_RTOL`` of the parameter update (see there).

Phase B, the swarm placement search on the Pallas kernel: a 20-particle
``FlagSwapPSO.run`` for 20 iterations over the ``large-10k`` cost model
(10,000 clients, 1,365 slots) with ``batch_fitness_fn`` left to
auto-select. The path taken must be the compiled Pallas kernel (its
executable holds a ``tpu_custom_call``), every batch of TPDs must match
the float64 numpy oracle within ``F32_RTOL``, and so must the final
gbest's TPD against the scalar ``CostModel.tpd``.

``--chips 4`` runs only the device-sharded pooled TPD evaluator: 4
``large-10k`` pools, 256 placement rows split over the 4 chips
(``PooledTPDEvaluator(shard="auto")``), against the same evaluator's
float64 numpy path at the parity suite's tolerance.

The script refuses to run (non-zero exit, no result line) when JAX
finds no TPU. Every check is printed; the last stdout line is one JSON
object, ``{"ok": true, "device": {...}}``, printed only when every check
passed. JAX's persistent compilation cache is on (see
``repro.utils.compile_cache``); the compile seconds of each phase are
printed, with the cache's hits and the compile time they saved.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

# TPU f32 matmuls run one bf16 pass by default (unit roundoff 2**-9,
# about 2e-3, on every product's inputs). Over 3 rounds x 2 local SGD
# steps through 4 layers that relative error reaches the parameter
# update itself, but not the parameters' size: the bound is on
# ||P_tpu - P_cpu|| / ||P_cpu - P_init||, i.e. 5% of the update.
PARAM_UPDATE_RTOL = 5e-2
# the parity suite's tolerance for f32 TPD paths against float64
F32_RTOL = 2e-5
# the parity suite's tolerance for the sharded float64 pooled path
F64_RTOL = 1e-12


class Checks:
    """Named pass/fail checks, printed as they are made."""

    def __init__(self):
        self.failed = []

    def add(self, name: str, ok: bool, detail: str) -> None:
        print(f"check {name}: {'PASS' if ok else 'FAIL'} ({detail})",
              flush=True)
        if not ok:
            self.failed.append(name)


class CompileMeter:
    """Seconds spent in XLA compilation and persistent-cache hits, read
    from ``jax.monitoring`` events (a cache hit's retrieval counts as
    compile time; ``saved_s`` is what the hits saved)."""

    def __init__(self):
        self.compile_s = self.saved_s = 0.0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration
        elif event == "/jax/compilation_cache/compile_time_saved_sec":
            self.saved_s += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self) -> tuple:
        return self.compile_s, self.saved_s, self.hits

    def since(self, snap: tuple) -> str:
        c, s, h = snap
        return (f"compile {self.compile_s - c:.3f} s, cache hits "
                f"{self.hits - h}, saved {self.saved_s - s:.3f} s")


def _recording(spec):
    """``spec`` as a scenario that keeps every environment it builds —
    how the script reads each run's final global parameters after
    ``run_experiment`` returns."""
    built = []

    @dataclasses.dataclass(frozen=True)
    class Recording(type(spec)):
        def make_environment(self, seed=0, eval_config=None):
            env = super().make_environment(seed, eval_config=eval_config)
            built.append(env)
            return env

    fields = {f.name: getattr(spec, f.name)
              for f in dataclasses.fields(spec)}
    return Recording(**fields), built


def _flat(tree) -> np.ndarray:
    return np.concatenate([np.asarray(x, np.float64).ravel()
                           for x in jax.tree.leaves(tree)])


def _initial_params(spec):
    """The global parameters a seed-0 emulated run of ``spec`` starts
    from (the orchestrator initializes from ``jax.random.key(seed)``)."""
    from repro.configs import get_config
    from repro.models import get_model
    return get_model(get_config(spec.model)).init(jax.random.key(0))


def phase_a(checks: Checks, meter: CompileMeter) -> None:
    from repro.experiments import get_scenario, run_experiment

    strategies = ["pso", "random"]
    results, params = {}, {}
    cpu = jax.devices("cpu")[0]
    for where in ("tpu", "cpu"):
        spec, envs = _recording(get_scenario("paper-fig4"))
        snap = meter.snapshot()
        t0 = time.perf_counter()
        if where == "tpu":
            res = run_experiment(spec, strategies, rounds=3, seeds=(0,),
                                 progress=False)
        else:
            with jax.default_device(cpu):
                res = run_experiment(spec, strategies, rounds=3,
                                     seeds=(0,), progress=False)
        wall = time.perf_counter() - t0
        on = {str(leaf.devices().pop().platform) for env in envs
              for leaf in jax.tree.leaves(env.orchestrator.params)}
        print(f"phase A [{where}] paper-fig4 x {strategies}, 3 rounds: "
              f"{wall:.3f} s wall, {meter.since(snap)}; params on {on}",
              flush=True)
        checks.add(f"A.{where}.params_on_{where}", on == {where}, str(on))
        results[where] = res
        params[where] = [env.orchestrator.params for env in envs]

    with jax.default_device(cpu):
        init = _initial_params(get_scenario("paper-fig4"))
    for i, name in enumerate(strategies):
        run_t, run_c = results["tpu"].runs[i], results["cpu"].runs[i]
        losses = np.asarray(run_t.metrics["loss"])
        checks.add(f"A.{name}.losses_finite",
                   bool(np.all(np.isfinite(losses))), f"{losses.tolist()}")
        checks.add(f"A.{name}.tpd_series_equal_cpu",
                   run_t.tpds == run_c.tpds,
                   f"tpu {run_t.tpds} cpu {run_c.tpds}")
        p_t, p_c = _flat(params["tpu"][i]), _flat(params["cpu"][i])
        update = np.linalg.norm(p_c - _flat(init))
        rel = float(np.linalg.norm(p_t - p_c) / update)
        dloss = float(np.max(np.abs(losses
                                    - np.asarray(run_c.metrics["loss"]))))
        checks.add(f"A.{name}.final_params_vs_cpu",
                   rel <= PARAM_UPDATE_RTOL,
                   f"|P_tpu-P_cpu|/|P_cpu-P_init| = {rel:.6g} "
                   f"<= {PARAM_UPDATE_RTOL}; |update| = {update:.6g}; "
                   f"max |loss_tpu-loss_cpu| = {dloss:.6g}")


def phase_b(checks: Checks, meter: CompileMeter) -> None:
    from repro.core.cost_model import PooledTPDEvaluator
    from repro.core.pso import FlagSwapPSO
    from repro.experiments import get_scenario
    from repro.kernels.tpd import batch_tpd_pallas

    cm = get_scenario("large-10k").make_environment(0).cost_model
    h = cm.hierarchy
    n_particles, iterations = 20, 20
    path = cm.tpd_path(n_particles)
    print(f"phase B large-10k: {h.total_clients} clients, {h.dimensions} "
          f"slots, {n_particles} particles -> batch_tpd path {path!r}",
          flush=True)
    checks.add("B.path_is_pallas", path == "pallas", path)

    sds = (jax.ShapeDtypeStruct((n_particles, h.dimensions), jnp.int32),
           jax.ShapeDtypeStruct((3, h.total_clients), jnp.float32),
           jax.ShapeDtypeStruct((n_particles, h.n_leaves), jnp.float32))
    hlo = batch_tpd_pallas.lower(
        *sds, depth=h.depth, width=h.width, penalty=cm.memory_penalty,
        interpret=False).compile().as_text()
    checks.add("B.executable_has_tpu_custom_call",
               "tpu_custom_call" in hlo, "compiled batch_tpd_pallas")

    oracle = PooledTPDEvaluator([cm], shard="off")
    worst = [0.0]

    def fitness(placements):
        f = cm.batch_fitness(placements)
        want = oracle.tpds(placements, np.zeros(len(placements), np.int64))
        worst[0] = max(worst[0],
                       float(np.max(np.abs(-f - want) / np.abs(want))))
        return f

    pso = FlagSwapPSO(h.dimensions, h.total_clients,
                      n_particles=n_particles, seed=0)
    snap = meter.snapshot()
    t0 = time.perf_counter()
    best = pso.run(cm.fitness, iterations=iterations,
                   batch_fitness_fn=fitness)
    wall = time.perf_counter() - t0
    print(f"phase B FlagSwapPSO.run {iterations} iterations x "
          f"{n_particles} particles (oracle checks included): "
          f"{wall:.3f} s wall, {meter.since(snap)}", flush=True)
    checks.add("B.batches_match_f64_oracle", worst[0] <= F32_RTOL,
               f"max rel err {worst[0]:.3g} <= {F32_RTOL} over "
               f"{iterations} batches")
    scalar = cm.tpd(best)
    rel = abs(-pso.gbest_f - scalar) / scalar
    checks.add("B.gbest_tpd_matches_scalar", rel <= F32_RTOL,
               f"gbest {-pso.gbest_f:.9g} vs tpd {scalar:.9g}, "
               f"rel {rel:.3g}")


def phase_sharded(checks: Checks, meter: CompileMeter) -> None:
    from repro.core.cost_model import PooledTPDEvaluator
    from repro.experiments import get_scenario
    from repro.fl.distributed import shard_rows
    from repro.launch.mesh import make_mesh

    ndev, n_rows = jax.local_device_count(), 256
    checks.add("C.four_devices", ndev == 4, f"{ndev} local devices")
    spec = get_scenario("large-10k")
    models = [spec.make_environment(s).cost_model for s in range(4)]
    h = models[0].hierarchy
    rng = np.random.default_rng(0)
    ps = np.stack([rng.permutation(h.total_clients)[: h.dimensions]
                   for _ in range(n_rows)]).astype(np.int32)
    pool_idx = rng.integers(0, 4, n_rows)

    # which device scores which row, on the evaluator's mesh shape
    mesh = make_mesh((ndev,), ("rows",), devices=jax.local_devices()[:ndev])
    owner = shard_rows(lambda p: jnp.zeros(p.shape[0], jnp.int32)
                       + jax.lax.axis_index("rows"), mesh, n_rows)(ps)
    rows_per_device = {str(d): int(n) for d, n in zip(
        mesh.devices.ravel(),
        np.bincount(np.asarray(owner), minlength=ndev), strict=True)}
    print(f"phase C rows per device: {rows_per_device}", flush=True)
    checks.add("C.rows_split_evenly",
               len(set(rows_per_device.values())) == 1,
               str(rows_per_device))

    ev = PooledTPDEvaluator(models, shard="auto")
    snap = meter.snapshot()
    t0 = time.perf_counter()
    got = ev.tpds(ps, pool_idx)
    wall = time.perf_counter() - t0
    print(f"phase C sharded pooled tpds, {n_rows} rows x 4 large-10k "
          f"pools: {wall:.3f} s wall, {meter.since(snap)}", flush=True)
    want = PooledTPDEvaluator(models, shard="off").tpds(ps, pool_idx)
    err = float(np.max(np.abs(got - want)))
    scale = float(np.max(np.abs(want)))
    checks.add("C.sharded_matches_f64_numpy",
               err <= F64_RTOL * max(scale, 1.0),
               f"max abs err {err:.3g}, scale {scale:.6g}, "
               f"rtol {F64_RTOL}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded pooled TPD phase")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX's first device is "
              f"{dev.platform!r}); refusing to run", file=sys.stderr)
        return 2
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.utils.compile_cache import enable_compile_cache

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"device: {device}; compile cache: {enable_compile_cache()}",
          flush=True)
    checks, meter = Checks(), CompileMeter()
    phases = [phase_sharded] if args.chips == 4 else [phase_a, phase_b]
    for phase in phases:
        snap = meter.snapshot()
        t0 = time.perf_counter()
        phase(checks, meter)
        print(f"{phase.__name__}: {time.perf_counter() - t0:.3f} s, "
              f"{meter.since(snap)}", flush=True)
    if checks.failed:
        print(f"chip_smoke: FAILED checks: {checks.failed}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
