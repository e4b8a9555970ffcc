"""The control — the plain reference put in the program's place and
computed one precision below the configuration's — fails the cell's
limits, and the program passes them (CPU, the cells' rehearsal sizes).

    python3 -m pytest bench/tests
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
SCRIPT = """
import json, sys
sys.path.insert(0, {bench!r})
import calibrate, run
spec = run.cell_spec({cell!r})
traffic = json.loads((run.BENCH / "traffic" /
                      (spec["workload"]["traffic"] + ".json")).read_text())
for line in calibrate.readings({cell!r}, [2**31 + 5], 0.3,
                               ["program", "control"],
                               rehearsal={{"traffic": traffic["rehearsal"]}}):
    print(json.dumps(line))
"""


@pytest.mark.parametrize("cell", ["fig4-round", "cohort-round",
                                  "search-10k"])
def test_control_fails_program_passes(cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT.format(bench=str(BENCH), cell=cell)],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(x) for x in proc.stdout.strip().splitlines()]
    limits = json.loads((BENCH / "limits" / f"{cell}.json").read_text())
    by = {x["who"]: x["numbers"] for x in lines}
    assert all(v <= limits[k] for k, v in by["program"].items()), \
        by["program"]
    assert any(v > limits[k] for k, v in by["control"].items()), \
        by["control"]
