"""Architecture registry.

``get_config("kimi-k2-1t-a32b")`` returns the exact published config;
``get_config("deepseek-r1-chip")`` one chip's share of deepseek-r1 (see
:mod:`repro.configs.deepseek_r1`);
``ASSIGNED_ARCHS`` lists the 10 graded architectures in assignment order.
"""

from __future__ import annotations

from typing import Dict, List

from repro.configs.base import ModelConfig, MoEConfig, SSMConfig
from repro.configs.shapes import (
    ALL_SHAPES,
    DECODE_32K,
    InputShape,
    LONG_500K,
    PREFILL_32K,
    TRAIN_4K,
    applicable,
    reduced_shape,
    shape_by_name,
)

from repro.configs import (  # noqa: E402  (registry imports)
    arctic_480b,
    deepseek_r1,
    gemma3_4b,
    granite_3_2b,
    kimi_k2_1t_a32b,
    minitron_8b,
    phi3_medium_14b,
    qwen2_vl_2b,
    rwkv6_7b,
    whisper_base,
    zamba2_2_7b,
)

ASSIGNED_ARCHS: List[str] = [
    "granite-3-2b",
    "gemma3-4b",
    "minitron-8b",
    "phi3-medium-14b",
    "arctic-480b",
    "kimi-k2-1t-a32b",
    "zamba2-2.7b",
    "whisper-base",
    "rwkv6-7b",
    "qwen2-vl-2b",
]

REGISTRY: Dict[str, ModelConfig] = {
    m.CONFIG.arch_id: m.CONFIG
    for m in (
        granite_3_2b, gemma3_4b, minitron_8b, phi3_medium_14b, arctic_480b,
        kimi_k2_1t_a32b, zamba2_2_7b, whisper_base, rwkv6_7b, qwen2_vl_2b,
        deepseek_r1,
    )
}
REGISTRY[deepseek_r1.CHIP.arch_id] = deepseek_r1.CHIP


def get_config(arch_id: str) -> ModelConfig:
    if arch_id not in REGISTRY:
        raise KeyError(f"unknown arch {arch_id!r}; have {sorted(REGISTRY)}")
    return REGISTRY[arch_id]


__all__ = [
    "ModelConfig", "MoEConfig", "SSMConfig", "InputShape",
    "ALL_SHAPES", "TRAIN_4K", "PREFILL_32K", "DECODE_32K", "LONG_500K",
    "ASSIGNED_ARCHS", "REGISTRY", "get_config", "shape_by_name",
    "applicable", "reduced_shape",
]
