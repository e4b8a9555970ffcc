"""Operations and bytes of a client language model's federated round,
counted from its sizes and from the token-expert pairs that its held
experts computed (the counts follow the mathematics, not any kernel).

Per held token-expert pair, the routed expert's gated FFN is three
matmuls of D x F: 6DF operations forward, twice that backward (the
inputs' and the weights' gradients), so 18DF for a trained pair and 6DF
for an evaluated one.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Shape:
    d: int              # hidden size
    f: int              # routed expert width
    held: int           # experts held here
    moe_layers: int
    dense_params: int   # matmul weights every token meets (not the
    #                     routed experts, not the embedding lookup)
    attn_layers: int
    heads: int
    qk_dim: int
    v_dim: int

    @classmethod
    def of(cls, m) -> "Shape":
        """From a program ``ModelConfig`` with ``mla`` and ``moe``."""
        d, h, a, e = m.d_model, m.n_heads, m.mla, m.moe
        attn = d * h * (a.qk_nope_head_dim + a.qk_rope_head_dim) \
            + d * (a.kv_lora_rank + a.qk_rope_head_dim) \
            + a.kv_lora_rank * h * (a.qk_nope_head_dim + a.v_head_dim) \
            + h * a.v_head_dim * d
        moe_layers = m.n_layers - m.first_k_dense
        shared = 3 * d * e.d_ff_expert * e.n_shared_experts
        dense = (m.n_layers * attn + m.first_k_dense * 3 * d * m.d_ff
                 + moe_layers * (shared + d * e.n_experts)
                 + d * m.padded_vocab)
        return cls(d=d, f=e.d_ff_expert, held=e.held, moe_layers=moe_layers,
                   dense_params=dense, attn_layers=m.n_layers, heads=h,
                   qk_dim=a.qk_nope_head_dim + a.qk_rope_head_dim,
                   v_dim=a.v_head_dim)


def expert_work(s: Shape, trained: float, evaluated: float, calls: int,
                eval_calls: int) -> tuple:
    """(operations, bytes) of the held experts' grouped FFN in one round.

    ``trained`` / ``evaluated``: held token-expert pairs computed in
    training (forward and backward) and in evaluation (forward);
    ``calls`` / ``eval_calls``: (sequence-step, MoE layer) pairs, each of
    which runs the three grouped matmuls once forward (and three times
    two backward). Bytes, bfloat16 weights and rows with float32 outputs:
    per call the held weights read forward (3 n D F x 2) and, in
    training, read again and their gradients written backward (2 x 3 n D
    F x 2); per pair the rows in (x twice, h once: (2D + F) x 2) and out
    ((2F + D) x 4) forward, three times that in training."""
    ops = 18 * s.d * s.f * trained + 6 * s.d * s.f * evaluated
    weights = 3 * s.held * s.d * s.f * 2
    rows = (2 * s.d + s.f) * 2 + (2 * s.f + s.d) * 4
    nbytes = (3 * weights * calls + weights * eval_calls
              + 3 * rows * trained + rows * evaluated)
    return float(ops), float(nbytes)


def attention_flops(s: Shape, seq_len: int) -> float:
    """Forward FLOPs of causal attention over one sequence, all layers:
    S^2/2 positions x heads x (qk + v dims) x 2."""
    return float(s.attn_layers * seq_len * seq_len * s.heads
                 * (s.qk_dim + s.v_dim))


def round_flops(s: Shape, tokens: int, seqs: int, trained: float,
                eval_tokens: int, evaluated: float) -> float:
    """Model FLOPs of one round: 6 x the matmul weights every token meets
    per trained token, 18DF per trained held pair, 3x the causal
    attention's forward per trained sequence, and the evaluation's
    forward (2x, 6DF per pair, 1x attention)."""
    train = (6.0 * s.dense_params * tokens + 18.0 * s.d * s.f * trained
             + 3.0 * seqs * attention_flops(s, tokens // seqs))
    ev = (2.0 * s.dense_params * eval_tokens + 6.0 * s.d * s.f * evaluated
          + attention_flops(s, eval_tokens))
    return train + ev
