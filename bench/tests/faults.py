"""Faults planted under the timed path, for ``test_faults.py``.

    python3 bench/tests/faults.py <cell> <fault>

runs one CPU rehearsal of ``<cell>`` (the small sizes of its traffic
file's ``rehearsal`` entry) with ``<fault>`` planted in the program, and
prints the result line. ``none`` plants nothing.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parents[1]
ALTER = 1e-3      # relative change of an altered answer


def state_unchanged():
    """A round that returns the global weights it started from."""
    from repro.fl.orchestrator import FederatedOrchestrator as F
    run_round = F.run_round

    def broken(self, r, placement):
        keep = self.params
        rec = run_round(self, r, placement)
        self.params = keep
        return rec
    F.run_round = broken


def half_batch_merge():
    """The merge leaves out the second half of the clients and takes the
    weighted mean over the rest."""
    from repro.fl.aggregation import SegmentAggregator as S
    fused = S.aggregate_fused

    def broken(self, stacked, weights, plan):
        w = np.asarray(weights, np.float64).copy()
        w[len(w) // 2:] = 0.0
        return fused(self, stacked, (w / w.sum()).astype(np.float32), plan)
    S.aggregate_fused = broken


def round_answer_altered():
    """Round 1's TPD is altered where the round produces it."""
    from repro.fl.orchestrator import FederatedOrchestrator as F
    run_round = F.run_round

    def broken(self, r, placement):
        rec = run_round(self, r, placement)
        if r == 1:
            rec.tpd *= 1 + ALTER
        return rec
    F.run_round = broken


def score_answer_altered():
    """One particle's score of every scored swarm is altered."""
    from repro.core.cost_model import CostModel as C
    batch_tpd = C.batch_tpd

    def broken(self, placements, backend=None):
        out = np.array(batch_tpd(self, placements, backend))
        out[0] *= 1 + ALTER
        return out
    C.batch_tpd = broken


def score_half_batch():
    """Half of every scored swarm is left out; its rows get the mean of
    the rest."""
    from repro.core.cost_model import CostModel as C
    batch_tpd = C.batch_tpd

    def broken(self, placements, backend=None):
        out = np.array(batch_tpd(self, placements, backend))
        h = len(out) // 2
        out[h:] = out[:h].mean()
        return out
    C.batch_tpd = broken


def swarm_frozen():
    """The swarm's update returns its state unchanged."""
    from repro.core.pso import FlagSwapPSO as P
    P._step_swarm = lambda self: None


def swarm_half_steps():
    """The swarm's update moves the first half of its particles only."""
    from repro.core.pso import FlagSwapPSO as P
    step = P._step_swarm

    def broken(self):
        h = self.n_particles // 2
        x, v = self.x[h:].copy(), self.v[h:].copy()
        step(self)
        self.x[h:], self.v[h:] = x, v
    P._step_swarm = broken


def gbest_not_kept():
    """The search returns its last swarm's best, not the best it scored."""
    from repro.core.pso import FlagSwapPSO as P

    def broken(self, fs):
        improved = fs > self.pbest_f
        self.pbest_f = np.where(improved, fs, self.pbest_f)
        self.pbest_x = np.where(improved[:, None], self.x, self.pbest_x)
        i = int(np.argmax(fs))
        self.gbest_f, self.gbest_x = float(fs[i]), self.x[i].copy()
    P._update_bests_swarm = broken


FAULTS = {
    "state_unchanged": state_unchanged,
    "half_batch_merge": half_batch_merge,
    "round_answer_altered": round_answer_altered,
    "score_answer_altered": score_answer_altered,
    "score_half_batch": score_half_batch,
    "swarm_frozen": swarm_frozen,
    "swarm_half_steps": swarm_half_steps,
    "gbest_not_kept": gbest_not_kept,
}


def main(cell: str, fault: str) -> int:
    sys.path.insert(0, str(BENCH))
    import run
    if fault != "none":
        FAULTS[fault]()
    spec = run.cell_spec(cell)
    traffic = json.loads((BENCH / "traffic" /
                          f"{spec['workload']['traffic']}.json").read_text())
    result = run.run_cell(cell, 2**31 + 23, 0.3, False,
                          rehearsal={"traffic": traffic["rehearsal"]})
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], sys.argv[2]))
