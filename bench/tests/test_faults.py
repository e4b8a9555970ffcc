"""The comparison that decides ``correct`` fails every fault a cell can
have, planted under the timed path (CPU, the cells' rehearsal sizes).

    python3 -m pytest bench/tests
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
CASES = [
    ("fig4-round", "none", True),
    ("fig4-round", "state_unchanged", False),
    ("fig4-round", "half_batch_merge", False),
    ("fig4-round", "round_answer_altered", False),
    ("cohort-round", "none", True),
    ("cohort-round", "state_unchanged", False),
    ("cohort-round", "half_batch_merge", False),
    ("cohort-round", "round_answer_altered", False),
    ("search-10k", "none", True),
    ("search-10k", "score_answer_altered", False),
    ("search-10k", "score_half_batch", False),
    ("search-10k", "swarm_frozen", False),
    ("search-10k", "swarm_half_steps", False),
    ("search-10k", "gbest_not_kept", False),
]


@pytest.mark.parametrize("cell,fault,correct", CASES)
def test_fault_decides_correct(cell, fault, correct):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, str(HERE / "faults.py"), cell,
                           fault], env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is correct, result["checks"]
