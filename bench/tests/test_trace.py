"""The trace reduction agrees with a brute-force recomputation and with
the values recorded beside each trace (CPU)."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import check_trace  # noqa: E402


def test_recorded_traces():
    assert check_trace.main([]) == 0
