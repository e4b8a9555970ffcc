"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``. Its
configuration (``bench/configs/<config>.json``, with the plain reference
``<config>.py`` beside it), its traffic (``bench/traffic/<traffic>.json``,
which names the driver in ``bench/drivers/``), the limits of its
comparison (``bench/limits/<cell>.json``) and the readers of its
per-layer metrics (``bench/metrics/<metric>.py``) are all found by name.

The run refuses to start without a TPU, or with fewer chips than the
cell asks for (exit 2, no result). Set-up (imports, inputs and weights
from the seed, warming every shape the cell's traffic uses) ends the
``setup_s`` clock; the window then runs for ``--seconds``; with
``--trace 1`` the window runs under the profiler and the per-layer
metrics are read from its trace and the benchmark's spans. After the
window, the peak device memory is read, the program's state is dropped,
and what the window produced is compared with the plain reference. The
numbers compared are printed with their limits as the last lines of
standard error and under ``checks`` in the result, the last line of
standard output.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
# the persistent compilation cache lives at a fixed path in the checkout
CACHE = ROOT / ".jax_cache"
CACHE.mkdir(exist_ok=True)
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE)
sys.path[:0] = [str(BENCH), str(ROOT / "src")]


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_spec(workload: str) -> dict:
    """Everything ``BENCHMARK.json`` says about one cell."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    w = cells[workload]
    config = {c["name"]: c for c in bench["configs"]}[w["config"]]

    def mine(m):
        return "workloads" not in m or workload in m["workloads"]

    return {"workload": w, "config": config,
            "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
            "per_layer": [m for m in bench["per_layer"] if mine(m)]}


def device_info(chips: int, rehearsal: bool) -> dict:
    import jax
    devs = jax.devices()
    if not rehearsal and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"this cell needs {chips} TPU chip(s); JAX finds "
                     f"{len(devs)} {devs[0].platform!r} device(s)")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


class NoChip(RuntimeError):
    pass


def memory_peak(n: int):
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:n]]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def load_cell(spec: dict, rehearsal: dict | None = None) -> tuple:
    """(configuration, traffic, reference module, driver module) of a
    cell, found by name; turns on the persistent compilation cache."""
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    file = spec["config"]["file"]
    cfg = json.loads((ROOT / file).read_text())
    traffic = json.loads((BENCH / "traffic" /
                          f"{spec['workload']['traffic']}.json").read_text())
    traffic.update((rehearsal or {}).get("traffic", {}))
    ref = load_module(ROOT / file.replace(".json", ".py"))
    driver = load_module(BENCH / "drivers" / f"{traffic['driver']}.py")
    return cfg, traffic, ref, driver


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             rehearsal: dict | None = None) -> dict:
    """One run of one cell; returns the result object.

    ``rehearsal`` (CPU rehearsals and the fault tests only) skips the
    look for a chip, merges its ``traffic`` entries over the traffic
    file, and leaves every device metric out of the result."""
    spec = cell_spec(workload)
    w = spec["workload"]
    device = device_info(w["chips"], rehearsal is not None)

    from harness.meter import CompileMeter, Spans
    meter = CompileMeter()
    cfg, traffic, ref, driver = load_cell(spec, rehearsal)
    limits = json.loads((BENCH / "limits" / f"{workload}.json").read_text())

    spans = Spans(annotate=trace)
    cell = driver.Cell(cfg, traffic, seed, ref, spans)
    cell.setup()
    setup_s = time.perf_counter() - T_START
    setup_compiles = meter.compiles
    spans.reset()

    reduced = None
    if trace:
        from harness import trace as tr
        with tr.Profile() as prof:
            with spans("window"):
                stats = cell.window(seconds)
            t0 = time.perf_counter()
        t1 = time.perf_counter()
        events = prof.events(spans.names())
        t2 = time.perf_counter()
        reduced = tr.reduce(events)
        print(f"trace: {t1 - t0:.3f} s to stop the profiler, {t2 - t1:.3f} s "
              f"to read its file, {time.perf_counter() - t2:.3f} s to "
              f"reduce it", file=sys.stderr)
    else:
        stats = cell.window(seconds)
    in_window = meter.compiles - setup_compiles
    device["memory_peak_bytes"] = memory_peak(w["chips"])
    facts = cell.facts()
    cell.release()

    verdict = cell.compare(cell.outputs("program"))
    numbers = verdict["numbers"]
    checks = {k: {"value": numbers[k], "limit": limits[k]}
              for k in sorted(numbers)}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    answers = verdict["answers"]
    failed = int((answers > limits[verdict["answer_limit"]]).sum())

    metrics, breakdown = {}, None
    if rehearsal is None and not trace:
        metrics = {m["name"]: {"value": setup_s if m["name"] == "setup_s"
                               else stats[m["name"]], "unit": m["unit"]}
                   for m in spec["end_to_end"]}
    elif rehearsal is None:
        from harness.peaks import peaks
        run = {"stats": stats, "facts": facts, "trace": reduced,
               "spans": dict(spans.seconds), "peaks": peaks(device["kind"]),
               "chips": w["chips"]}
        for m in spec["per_layer"]:
            value = load_module(BENCH / "metrics" / f"{m['name']}.py"
                                ).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        breakdown = reduced["breakdown"]

    print(f"{workload}: set-up {setup_s:.3f} s ({setup_compiles} compiles, "
          f"{meter.hits} cache hits, {meter.compile_s:.3f} s compiling); "
          f"window {stats['elapsed']:.3f} s, {stats['units']} "
          f"{cell.unit}; {in_window} compiles inside the window",
          file=sys.stderr)
    for k, c in checks.items():
        print(f"check {k}: {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['value'] <= c['limit'] else 'FAIL'}",
              file=sys.stderr, flush=True)
    result = {"correct": correct, "attempted": int(len(answers)),
              "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    if rehearsal is not None:
        result["names"] = [m["name"] for m in spec["end_to_end"]
                           + spec["per_layer"]]
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}; refusing to run", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
