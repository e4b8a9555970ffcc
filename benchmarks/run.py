"""Run every benchmark:  PYTHONPATH=src python -m benchmarks.run

The suite is a registered list — the ``i/N`` banner is derived from it,
so adding/skipping entries can never desynchronize the numbering.
Order: kernels (fast, also a correctness gate) -> Fig. 3 simulation ->
Fig. 4 cluster emulation -> the beyond-paper scenario benches ->
roofline (consumes dry-run artifacts if present). ``--full`` runs the
paper-scale 50-round Fig. 4; default is 25 rounds to keep the suite
under ~10 minutes on CPU.
"""
from __future__ import annotations

import argparse
import sys
import time


def _check_fig3(r):
    if not r["claims"]["tpd_converges"]:
        return "TPD did not converge in all cells"


def _check_fig4(r):
    if not r["claims"]["pso_faster_than_random"]:
        return "PSO not faster than random"


def _check_drift(r):
    if r["tail_gain_vs_frozen"] <= 0:
        return "adaptive did not beat frozen PSO"


def _check_optimizers(r):
    if not r["pso_competitive"]:
        return "PSO lost to random on cumulative TPD"


def _check_two_tier(r):
    if not r["locality_discovered"]:
        return "no pod locality discovered"


def _run_scenarios():
    """Smoke the event scenarios end-to-end through the experiment API."""
    from repro.experiments import run_experiment
    out, errs = {}, []
    for scenario in ("churn", "straggler", "latency"):
        print(f"-- scenario {scenario}")
        res = run_experiment(scenario, ["pso", "random"], rounds=40,
                             seeds=(0, 1))
        agg = res.aggregates
        out[scenario] = agg
        if agg["pso"]["total_tpd"] > agg["random"]["total_tpd"] * 1.25:
            errs.append(f"PSO >25% worse than random under {scenario}")
    return out, "; ".join(errs) or None


def build_suite(args):
    """[(name, thunk, checker)] — the single source of the banner."""
    from benchmarks import (bench_calibration, bench_drift, bench_faults,
                            bench_fig3_simulation, bench_fig4_cluster,
                            bench_kernels, bench_online,
                            bench_optimizers, bench_roofline,
                            bench_two_tier)

    def roofline():
        for mesh in ("16x16", "2x16x16"):
            bench_roofline.main(mesh=mesh)

    suite = [
        ("kernels", bench_kernels.main, None),
        ("Fig. 3 (simulation)", bench_fig3_simulation.main, _check_fig3),
    ]
    if not args.skip_fig4:
        rounds = 50 if args.full else 25
        suite.append(("Fig. 4 (cluster emulation)",
                      lambda: bench_fig4_cluster.main(rounds=rounds),
                      _check_fig4))
    suite += [
        ("drift adaptation (beyond paper)", bench_drift.main,
         _check_drift),
        ("optimizer shoot-out (beyond paper)", bench_optimizers.main,
         _check_optimizers),
        ("two-tier pod locality (beyond paper)", bench_two_tier.main,
         _check_two_tier),
        ("event scenarios via experiments API", _run_scenarios,
         lambda r: r[1]),
        ("online track (async vs lockstep)",
         lambda: bench_online.main(["--smoke"] if not args.full else []),
         lambda rc: "bench_online failed" if rc != 0 else None),
        ("fault track (survivability + recovery overhead)",
         lambda: bench_faults.main(["--smoke"] if not args.full else []),
         lambda rc: "bench_faults failed" if rc != 0 else None),
        ("calibration (record -> fit -> replay)",
         lambda: bench_calibration.main(
             ["--smoke"] if not args.full else []),
         lambda rc: "bench_calibration failed" if rc != 0 else None),
        ("roofline", roofline, None),
    ]
    return suite


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale Fig. 4 (50 rounds)")
    ap.add_argument("--skip-fig4", action="store_true")
    args = ap.parse_args()

    t0 = time.time()
    failures = []
    suite = build_suite(args)
    total = len(suite)
    for i, (name, thunk, check) in enumerate(suite, start=1):
        print(f"\n##### {i}/{total} {name} #####")
        try:
            result = thunk()
            if check is not None:
                err = check(result)
                if err:
                    failures.append((name, err))
        except Exception as e:
            failures.append((name, repr(e)))
            print(f"FAILED: {e!r}")

    dt = time.time() - t0
    if failures:
        print(f"\n== benchmarks: {len(failures)} FAILURE(S) in {dt:.0f}s ==")
        for name, err in failures:
            print(f"  {name}: {err}")
        return 1
    print(f"\n== all benchmarks passed in {dt:.0f}s ==")
    return 0


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    sys.exit(main())
