"""Operations and bytes a cell's work requires, counted from its sizes.

The counts follow the mathematics of the work, not any implementation,
so they read the same whatever computes it.
"""
from __future__ import annotations

# Eq. 6/7 for one placement, counted once each (``tpd_work``):
#   add   - every trainer's payload into its leaf        (C - D)
#   add   - every inner slot's kid payloads               (D - 1)
#   add   - every slot host's own payload                 (D)
#   div   - eq. 6, load / pspeed of the host              (D)
#   max   - every slot's delay into its level's maximum   (D)
#   add   - the level maxima into the TPD                 (depth)
# with the memcap penalty on, four more per slot (sub, max, div, fma).
# Bytes: the placement ids read (D x 4), the host attributes read once
# (mdatasize and pspeed, and memcap with the penalty, C x 4 each) and
# the TPD written (4), all float32 / int32.
TPD_OPS = ("trainer payload add", "kid payload add", "own payload add",
           "eq. 6 divide", "level max", "level sum")


def tpd_work(P: int, D: int, C: int, depth: int,
             penalty: bool = False) -> tuple:
    """(ops, bytes) of scoring ``P`` placements of ``D`` slots over ``C``
    clients on a ``depth``-level tree."""
    per = (C - D) + (D - 1) + D + D + D + depth + (4 * D if penalty else 0)
    attrs = 3 if penalty else 2
    return P * per, 4 * (P * D + attrs * C + P)


def mlp_params(model_init) -> int:
    """N, the parameter count of a model, from ``jax.eval_shape`` of its
    ``init(key)``: nothing is computed."""
    import math

    import jax
    shapes = jax.eval_shape(model_init, jax.random.key(0))
    return sum(math.prod(x.shape) for x in jax.tree.leaves(shapes))


def mlp_round_flops(n_params: int, trained_samples: int,
                    eval_samples: int) -> float:
    """Model FLOPs of one federated round: 6N per trained sample (forward
    and backward) and 2N per evaluated sample (forward)."""
    return 6.0 * n_params * trained_samples + 2.0 * n_params * eval_samples
