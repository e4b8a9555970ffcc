"""The two cells of the DeepSeek-V2-Lite configuration's PR at their CPU
rehearsal sizes: the program passes its limits; the control (the plain
reference one precision down) fails them; the faults read in the
reference (half the silos merged, one held expert's output dropped) fail
them too.

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
for line in calibrate.readings({cell!r}, [2**33 + 7], 0.3, {who!r},
                               rehearsal={{"traffic": traffic["rehearsal"]}}):
    print(json.dumps(line))
"""


def _readings(cell, who, devices=1):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    if devices > 1:
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " --xla_force_"
                            f"host_platform_device_count={devices}")
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT.format(bench=str(BENCH), cell=cell,
                                             who=who)],
        env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    limits = json.loads((BENCH / "limits" / f"{cell}.json").read_text())
    return {x["who"]: x["numbers"] for x in
            map(json.loads, proc.stdout.strip().splitlines())}, limits


def test_lm_cell_program_passes_control_and_faults_fail():
    by, limits = _readings("silo8-dsv2lite",
                           ["program", "control", "half_batch",
                            "drop_expert"])
    assert all(v <= limits[k] for k, v in by["program"].items()), \
        by["program"]
    for who in ("control", "half_batch", "drop_expert"):
        assert any(v > limits[k] for k, v in by[who].items()), (who, by[who])


@pytest.mark.parametrize("who,fails", [("program", False), ("control", True)])
def test_sweep_cell_program_passes_control_fails(who, fails):
    by, limits = _readings("sweep-10k-x4", [who], devices=4)
    assert any(v > limits[k] for k, v in by[who].items()) == fails, by[who]
