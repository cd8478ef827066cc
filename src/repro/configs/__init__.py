"""Architecture registry: --arch <id> resolution for launchers and tests."""
from __future__ import annotations

import importlib

from repro.configs.base import (SHAPES, SHAPES_BY_NAME, ModelConfig,
                                ShapeConfig, shape_applicable)

_MODULES = {
    "musicgen-medium": "repro.configs.musicgen_medium",
    "rwkv6-7b": "repro.configs.rwkv6_7b",
    "chameleon-34b": "repro.configs.chameleon_34b",
    "yi-6b": "repro.configs.yi_6b",
    "qwen1.5-4b": "repro.configs.qwen1_5_4b",
    "smollm-360m": "repro.configs.smollm_360m",
    "deepseek-7b": "repro.configs.deepseek_7b",
    "deepseek-v2-236b": "repro.configs.deepseek_v2_236b",
    "deepseek-v2-lite": "repro.configs.deepseek_v2_lite",
    "llama4-maverick-400b-a17b": "repro.configs.llama4_maverick_400b",
    "zamba2-1.2b": "repro.configs.zamba2_1_2b",
}

ARCH_IDS = tuple(_MODULES)


def get_config(arch_id: str) -> ModelConfig:
    if arch_id not in _MODULES:
        raise KeyError(f"unknown arch {arch_id!r}; known: {sorted(_MODULES)}")
    return importlib.import_module(_MODULES[arch_id]).CONFIG


def get_smoke_config(arch_id: str) -> ModelConfig:
    return importlib.import_module(_MODULES[arch_id]).smoke()


__all__ = ["ModelConfig", "ShapeConfig", "SHAPES", "SHAPES_BY_NAME",
           "ARCH_IDS", "get_config", "get_smoke_config", "shape_applicable"]
