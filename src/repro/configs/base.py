"""Config dataclasses for the model zoo, input shapes, and distribution.

A config is plain data: the model builders in ``repro.models`` consume it,
``repro.launch.dryrun`` lowers it, and the FL layer federates it. Every
assigned architecture gets one file in this package with the exact
published numbers (source cited in its docstring) plus a ``reduced()``
variant used by the CPU smoke tests (2 layers, d_model <= 512,
<= 4 experts, tiny vocab).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Optional


@dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_ff_expert: int
    # router load-balance auxiliary loss weight (Switch-style; on the
    # dropless path DeepSeek's sequence-level loss, summed over layers)
    router_aux_weight: float = 0.01
    # capacity factor used to bound expert buffers in the dense-dispatch
    # path; None = dropless (DeepSeekMoE): every routed token-expert pair
    # is computed, as a grouped matmul over the held experts, and the
    # top-k weights are neither renormalized nor scaled (DeepSeek-V2)
    capacity_factor: Optional[float] = 1.25
    # shared experts applied to every token, as one gated MLP of width
    # n_shared_experts * d_ff_expert
    n_shared_experts: int = 0
    # expert parallelism as seen from one chip: this chip holds experts
    # [expert_shard * n_held, (expert_shard + 1) * n_held) of n_experts;
    # the router still scores all n_experts. None = every expert held.
    n_held: Optional[int] = None
    expert_shard: int = 0

    @property
    def held(self) -> int:
        return self.n_experts if self.n_held is None else self.n_held


@dataclass(frozen=True)
class MLAConfig:
    """Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434 §2.1):
    a low-rank joint KV latent with decoupled rotary keys. No query LoRA."""
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int


@dataclass(frozen=True)
class YarnScaling:
    """YaRN rotary scaling (the ``rope_scaling`` block of a HF config)."""
    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str  # dense | moe | ssm | hybrid | audio | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: Optional[int] = None  # default: d_model // n_heads
    moe: Optional[MoEConfig] = None
    # leading dense layers before the MoE stack (DeepSeek's
    # first_k_dense_replace); they use the dense FFN of width d_ff
    first_k_dense: int = 0
    mla: Optional[MLAConfig] = None
    rope_scaling: Optional[YarnScaling] = None

    # --- attention variants -------------------------------------------------
    rope_theta: float = 10_000.0
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    # sliding window (tokens). None => full causal attention. The long_500k
    # shape forces a window for attention archs (see ShapeConfig.window_override)
    sliding_window: Optional[int] = None

    # --- hybrid (RecurrentGemma / Griffin) ----------------------------------
    # pattern "2r1a" = 2 RG-LRU blocks then 1 local-attention block, repeated
    hybrid_pattern: str = ""
    local_attn_window: int = 2048
    rglru_dim: Optional[int] = None  # defaults to d_model

    # --- ssm (xLSTM) ----------------------------------------------------------
    # fraction/positions of sLSTM blocks; remaining are mLSTM.  "alt" =>
    # alternate mLSTM/sLSTM.  xlstm d_ff==0 means the block carries its own
    # up/down projections (proj_factor).
    xlstm_slstm_every: int = 2
    xlstm_proj_factor: float = 2.0
    xlstm_chunk: int = 256

    # --- enc-dec (audio) ------------------------------------------------------
    n_encoder_layers: int = 0  # >0 => encoder-decoder model
    # stub modality frontend: shape of precomputed embeddings
    frontend_len: int = 0      # audio frames / vision patches per example
    frontend_dim: int = 0      # embedding dim produced by the (stub) frontend

    # --- numerics / compile policy -------------------------------------------
    dtype: str = "bfloat16"
    param_dtype: str = "float32"
    remat: bool = True
    vocab_pad_multiple: int = 256
    # FSDP-shard params over the data axis (ZeRO-3 style) for big models
    fsdp: bool = False

    citation: str = ""

    # ------------------------------------------------------------------
    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim if self.head_dim is not None else self.d_model // self.n_heads

    @property
    def padded_vocab(self) -> int:
        m = self.vocab_pad_multiple
        return ((self.vocab_size + m - 1) // m) * m

    @property
    def is_encoder_decoder(self) -> bool:
        return self.n_encoder_layers > 0

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    def reduced(self) -> "ModelConfig":
        """Smoke-test variant: same family/block structure, tiny dims."""
        d_model = min(self.d_model, 256)
        n_heads = min(self.n_heads, 4)
        n_kv = max(1, min(self.n_kv_heads, n_heads))
        moe = None
        if self.moe is not None:
            n_experts = min(self.moe.n_experts, 4)
            moe = dataclasses.replace(
                self.moe, n_experts=n_experts,
                top_k=min(self.moe.top_k, 2), d_ff_expert=64,
                n_held=None if self.moe.n_held is None
                else min(self.moe.n_held, n_experts),
                expert_shard=0)
        mla = None
        if self.mla is not None:
            mla = MLAConfig(kv_lora_rank=32, qk_nope_head_dim=16,
                            qk_rope_head_dim=8, v_head_dim=16)
        return self.replace(
            # the leading dense layers plus two of the repeated ones
            n_layers=min(self.first_k_dense, 1) + 2,
            first_k_dense=min(self.first_k_dense, 1),
            mla=mla,
            d_model=d_model,
            n_heads=n_heads,
            n_kv_heads=n_kv,
            head_dim=d_model // n_heads,
            d_ff=min(self.d_ff, 4 * d_model) if self.d_ff else 0,
            vocab_size=512,
            moe=moe,
            n_encoder_layers=2 if self.n_encoder_layers else 0,
            frontend_len=8 if self.frontend_len else 0,
            frontend_dim=d_model if self.frontend_dim else 0,
            rglru_dim=d_model if self.rglru_dim else None,
            local_attn_window=64,
            sliding_window=64 if self.sliding_window else None,
            xlstm_chunk=16,
            remat=False,
            fsdp=False,
            vocab_pad_multiple=64,
        )


@dataclass(frozen=True)
class ShapeConfig:
    """One of the four assigned input shapes."""
    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"
    # long-context decode forces sliding-window attention for attention archs
    window_override: Optional[int] = None


TRAIN_4K = ShapeConfig("train_4k", 4_096, 256, "train")
PREFILL_32K = ShapeConfig("prefill_32k", 32_768, 32, "prefill")
DECODE_32K = ShapeConfig("decode_32k", 32_768, 128, "decode")
LONG_500K = ShapeConfig("long_500k", 524_288, 1, "decode", window_override=4_096)

SHAPES = {s.name: s for s in (TRAIN_4K, PREFILL_32K, DECODE_32K, LONG_500K)}


@dataclass(frozen=True)
class FLConfig:
    """Federated-learning runtime knobs (the paper's system)."""
    n_clients: int = 10
    depth: int = 2
    width: int = 2
    rounds: int = 50
    local_steps: int = 1
    strategy: str = "pso"      # pso | random | uniform | ga | exhaustive | flat
    # PSO hyper-parameters — paper defaults (Sec. III-C / IV-B)
    pso_particles: int = 10
    pso_inertia: float = 0.01
    pso_c1: float = 0.01
    pso_c2: float = 1.0
    pso_velocity_factor: float = 0.1
    seed: int = 0
