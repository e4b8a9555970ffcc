"""CPU rehearsal of every cell: no chip, no device metric.

    python3 bench/rehearse.py [cell ...]

Each cell runs in a child process with ``JAX_PLATFORMS=cpu`` (Pallas
kernels interpreted) and, for a cell that asks for four chips,
``XLA_FLAGS=--xla_force_host_platform_device_count=4``. The child drives
the whole run at the small sizes of its traffic file's ``rehearsal``
entry, once untraced and once traced, for a fraction of a second, and
checks the shape of the result line; it prints no device metric.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def check_shape(result: dict, names: list) -> list:
    """What is wrong with a rehearsal's result line (empty when sound)."""
    bad = [f"missing {k}" for k in KEYS if k not in result]
    if list(result)[-1] != "checks":
        bad.append("checks is not the last key")
    if not isinstance(result.get("correct"), bool):
        bad.append("correct is not a bool")
    for k in ("attempted", "failed"):
        if not isinstance(result.get(k), int):
            bad.append(f"{k} is not an int")
    if result.get("metrics") != {}:
        bad.append("a rehearsal printed a metric")
    if set(result.get("device", {})) < {"platform", "kind", "count"}:
        bad.append("device lacks platform, kind or count")
    if result.get("names") != names:
        bad.append(f"metric names {result.get('names')} != {names}")
    for k, c in result.get("checks", {}).items():
        if set(c) != {"value", "limit"}:
            bad.append(f"check {k} is not a value beside a limit")
    return bad


def child(cell: str) -> int:
    sys.path.insert(0, str(BENCH))
    import run
    spec = run.cell_spec(cell)
    traffic = json.loads((BENCH / "traffic" /
                          f"{spec['workload']['traffic']}.json").read_text())
    names = [m["name"] for m in spec["end_to_end"] + spec["per_layer"]]
    ok = True
    for trace in (False, True):
        result = run.run_cell(cell, 2**31 + 11, 0.3, trace,
                              rehearsal={"traffic": traffic["rehearsal"]})
        line = json.loads(json.dumps(result))
        bad = check_shape(line, names)
        if not line["correct"]:
            bad.append("correct is false")
        print(f"rehearsal {cell} trace={int(trace)}: "
              f"{'ok' if not bad else 'FAILED: ' + '; '.join(bad)}",
              flush=True)
        ok = ok and not bad
    return 0 if ok else 1


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if argv[:1] == ["--child"]:
        return child(argv[1])
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    cells = argv or [w["name"] for w in bench["workloads"]]
    chips = {w["name"]: w["chips"] for w in bench["workloads"]}
    rc = 0
    for cell in cells:
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        if chips[cell] > 1:
            env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " --xla_force_"
                                f"host_platform_device_count={chips[cell]}")
        proc = subprocess.run([sys.executable, __file__, "--child", cell],
                              env=env, stdout=subprocess.PIPE, text=True,
                              timeout=900)
        sys.stdout.write(proc.stdout)
        rc = rc or proc.returncode
    return rc


if __name__ == "__main__":
    sys.exit(main())
