"""Readings that the limits of ``bench/limits/<cell>.json`` are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 3 \
        [--who program,control,half_batch] [--fault <name>]

For each seed, in one process: the cell's set-up and a short window at
the cell's own size, then every number the comparison reads, once for
each source named in ``--who``: ``program`` (what the timed path
produced), ``control`` (the plain reference put in the program's place,
computed one precision below the configuration's) and, for the emulated
cells, ``half_batch`` (the reference with half the clients left out of
the merge). ``--fault`` first plants one of the faults of
``bench/tests/faults.py`` in the program, so that ``program`` reads the
program at fault. One JSON line per seed and source. The benchmark's
own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
import run as bench  # noqa: E402


def readings(workload: str, seeds, seconds: float, who, rehearsal=None,
             fault=None):
    spec = bench.cell_spec(workload)
    if fault:
        sys.path.insert(0, str(bench.BENCH / "tests"))
        import faults
        faults.FAULTS[fault]()
    bench.device_info(spec["workload"]["chips"], rehearsal is not None)
    from harness.meter import Spans
    cfg, traffic, ref, driver = bench.load_cell(spec, rehearsal)
    for seed in seeds:
        cell = driver.Cell(cfg, traffic, seed, ref, Spans())
        cell.setup()
        cell.window(seconds)
        cell.release()
        for source in who:
            numbers = cell.compare(cell.outputs(source))["numbers"]
            yield {"workload": workload, "seed": seed, "who": source,
                   "fault": fault, "numbers": numbers}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--who", default="program,control")
    ap.add_argument("--fault", default=None)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    try:
        for line in readings(args.workload, seeds, args.seconds,
                             args.who.split(","), fault=args.fault):
            print(json.dumps(line), flush=True)
    except bench.NoChip as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
