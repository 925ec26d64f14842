"""Config registry: one module per assigned architecture (+ the paper's own

Qwen2.5 family used in the reproduction benchmarks). ``get_config(name)``
returns the full ArchConfig; ``--arch <id>`` in the launchers resolves here.
"""
from __future__ import annotations

from .base import (
    ArchConfig,
    EncDecConfig,
    HybridConfig,
    LoRAConfig,
    MLAConfig,
    MoEConfig,
    ShapeConfig,
    SHAPES,
    shape_applicable,
)

from . import (
    olmoe_1b_7b,
    deepseek_moe_16b,
    granite_8b,
    gemma3_12b,
    qwen2_5_32b,
    minitron_4b,
    internvl2_1b,
    whisper_tiny,
    rwkv6_1_6b,
    recurrentgemma_2b,
    qwen2_5_paper,
    moonlight_16b_a3b,
)

_MODULES = [
    olmoe_1b_7b, deepseek_moe_16b, granite_8b, gemma3_12b, qwen2_5_32b,
    minitron_4b, internvl2_1b, whisper_tiny, rwkv6_1_6b, recurrentgemma_2b,
]

REGISTRY = {}
for _m in _MODULES:
    REGISTRY[_m.CONFIG.name] = _m.CONFIG
for _c in qwen2_5_paper.CONFIGS + moonlight_16b_a3b.CONFIGS:
    REGISTRY[_c.name] = _c

ASSIGNED = tuple(m.CONFIG.name for m in _MODULES)


def get_config(name: str) -> ArchConfig:
    if name not in REGISTRY:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(REGISTRY)}")
    return REGISTRY[name]


__all__ = [
    "ArchConfig", "LoRAConfig", "MLAConfig", "MoEConfig", "HybridConfig", "EncDecConfig",
    "ShapeConfig", "SHAPES", "shape_applicable", "REGISTRY", "ASSIGNED",
    "get_config",
]
