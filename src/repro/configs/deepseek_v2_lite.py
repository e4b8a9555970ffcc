"""deepseek-v2-lite — [moe] 27L d_model=2048 16H MLA (kv_lora 512, qk 128+64
rope, v 128, YaRN x40), layer 0 dense (d_ff 10944), 26 DeepSeekMoE layers
of 64 routed experts (width 1408, top-6, softmax, no renormalization)
plus 2 shared experts, vocab 102400 untied, 15.7B total / ~2.4B active.
[hf:deepseek-ai/DeepSeek-V2-Lite, config.json; arXiv:2405.04434 §2.1-2.2]

The balance-loss weight (``aux_loss_alpha`` 0.001) is not in the
catalog's copy of the config; it is DeepSeek-V2's published value.
Every expert is held here (``n_held`` None); a deployment that splits
the experts over chips sets ``n_held`` and ``expert_shard``.
"""
from repro.configs.base import MLAConfig, ModelConfig, MoEConfig, YarnScaling

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    family="moe",
    n_layers=27,
    first_k_dense=1,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=10_944,
    vocab_size=102_400,
    moe=MoEConfig(n_experts=64, top_k=6, d_ff_expert=1408,
                  router_aux_weight=0.001, capacity_factor=None,
                  n_shared_experts=2),
    mla=MLAConfig(kv_lora_rank=512, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128),
    rope_theta=10_000.0,
    rope_scaling=YarnScaling(factor=40.0, original_max_position=4096,
                             beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                             mscale_all_dim=0.707),
    norm_eps=1e-6,
    tie_embeddings=False,
    citation="hf:deepseek-ai/DeepSeek-V2-Lite",
)
