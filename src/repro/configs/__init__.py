"""Architecture config registry.

``get_config(name)`` returns the exact published configuration;
``get_config(name).reduced()`` is the CPU smoke-test variant.
"""
from __future__ import annotations

from repro.configs.base import (
    DECODE_32K,
    LONG_500K,
    PREFILL_32K,
    SHAPES,
    TRAIN_4K,
    FLConfig,
    MLAConfig,
    ModelConfig,
    MoEConfig,
    ShapeConfig,
    YarnScaling,
)
from repro.configs.deepseek_v2_lite import CONFIG as _dsv2_lite
from repro.configs.granite_8b import CONFIG as _granite_8b
from repro.configs.granite_moe_1b_a400m import CONFIG as _granite_moe
from repro.configs.llava_next_mistral_7b import CONFIG as _llava
from repro.configs.minitron_8b import CONFIG as _minitron
from repro.configs.paper_mlp import CONFIG as _paper_mlp, CONFIG_SMOKE as _mlp_smoke
from repro.configs.qwen3_moe_235b_a22b import CONFIG as _qwen3_moe
from repro.configs.recurrentgemma_2b import CONFIG as _rgemma
from repro.configs.seamless_m4t_large_v2 import CONFIG as _seamless
from repro.configs.stablelm_1_6b import CONFIG as _stablelm16b
from repro.configs.stablelm_3b import CONFIG as _stablelm3b
from repro.configs.xlstm_1_3b import CONFIG as _xlstm

_REGISTRY = {
    c.name: c
    for c in (
        _qwen3_moe,
        _granite_8b,
        _xlstm,
        _seamless,
        _granite_moe,
        _llava,
        _minitron,
        _rgemma,
        _stablelm3b,
        _stablelm16b,
        _paper_mlp,
        _mlp_smoke,
        _dsv2_lite,
    )
}

# the ten assigned architectures (paper_mlp is extra: the paper's own workload)
ASSIGNED = [
    "qwen3-moe-235b-a22b",
    "granite-8b",
    "xlstm-1.3b",
    "seamless-m4t-large-v2",
    "granite-moe-1b-a400m",
    "llava-next-mistral-7b",
    "minitron-8b",
    "recurrentgemma-2b",
    "stablelm-3b",
    "stablelm-1.6b",
]


def get_config(name: str) -> ModelConfig:
    if name not in _REGISTRY:
        raise KeyError(f"unknown architecture {name!r}; known: {sorted(_REGISTRY)}")
    return _REGISTRY[name]


def list_configs() -> list[str]:
    return sorted(_REGISTRY)


def get_shape(name: str) -> ShapeConfig:
    if name not in SHAPES:
        raise KeyError(f"unknown shape {name!r}; known: {sorted(SHAPES)}")
    return SHAPES[name]


__all__ = [
    "ModelConfig", "MoEConfig", "MLAConfig", "YarnScaling", "ShapeConfig",
    "FLConfig",
    "SHAPES", "TRAIN_4K", "PREFILL_32K", "DECODE_32K", "LONG_500K",
    "ASSIGNED", "get_config", "get_shape", "list_configs",
]
