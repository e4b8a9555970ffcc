"""Plain reference of the ``large-10k`` deployment: the eq. 6/7 cost of
a placement (paper Sec. III, eqs. 6-7).

    d_a = (mdatasize_a + sum_{c in children(a)} mdatasize_c) / pspeed_a
    TPD = sum over levels of max_{a in level} d_a

with the optional memcap penalty d_a *= 1 + penalty * max(0, load -
memcap_a) / memcap_a. Computed in ``dtype``: float64 is the reference,
a lower one is the control.
"""
from __future__ import annotations

import numpy as np

from harness import tree


def tpd(placements: np.ndarray, pool: dict, depth: int, width: int,
        penalty: float = 0.0, dtype=np.float64) -> np.ndarray:
    """(R, D) placements against one pool -> (R,) TPDs."""
    placements = np.asarray(placements, np.int64)
    load, _ = tree.slot_loads(placements, pool["mdatasize"], depth, width,
                              dtype)
    pspeed = np.asarray(pool["pspeed"]).astype(dtype)[placements]
    delay = load / pspeed
    if penalty > 0:
        cap = np.asarray(pool["memcap"]).astype(dtype)[placements]
        over = np.maximum(dtype(0), load - cap)
        delay = delay * (dtype(1) + dtype(penalty) * over /
                         np.maximum(cap, dtype(1e-9)))
    return tree.sum_of_level_max(delay, depth, width)
