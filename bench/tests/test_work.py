"""Operation, byte and peak tables of the benchmark (CPU)."""
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

from harness import peaks, work  # noqa: E402


def test_mlp_parameter_count_from_eval_shape():
    from repro.configs import get_config
    from repro.models import get_model
    cfg = json.loads((BENCH / "configs" / "paper-mlp-1m8.json").read_text())
    model = get_model(get_config(cfg["program_model"]))
    assert work.mlp_params(model.init) == cfg["n_params"] == 1_791_754


def test_mlp_round_flops():
    assert work.mlp_round_flops(10, 3, 2) == 6 * 10 * 3 + 2 * 10 * 2


def test_tpd_work_counts_each_named_op_once():
    # one placement, 3 slots (depth 2, width 2), 7 clients, no penalty:
    # 4 trainer adds, 2 kid adds, 3 own adds, 3 divides, 3 maxima, 2 sums
    ops, nbytes = work.tpd_work(1, 3, 7, 2)
    assert ops == 4 + 2 + 3 + 3 + 3 + 2
    assert nbytes == 4 * (3 + 2 * 7 + 1)
    assert len(work.TPD_OPS) == 6


def test_unlisted_device_kind_is_an_error():
    assert peaks.peaks("TPU v5 lite")["flops_bf16"] == 197e12
    with pytest.raises(ValueError):
        peaks.peaks("cpu")
