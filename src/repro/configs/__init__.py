"""Architecture registry: ``get_config(arch_id)`` + the assigned shape sets."""
from __future__ import annotations

import importlib

from .base import SHAPES, ModelConfig, ShapeConfig, reduced, shape_applicable

_ARCH_MODULES = {
    "llama4-scout-17b-a16e": "llama4_scout_17b_a16e",
    "qwen3-moe-30b-a3b": "qwen3_moe_30b_a3b",
    "qwen2.5-3b": "qwen2_5_3b",
    "glm4-9b": "glm4_9b",
    "minitron-8b": "minitron_8b",
    "minicpm-2b": "minicpm_2b",
    "mamba2-370m": "mamba2_370m",
    "whisper-small": "whisper_small",
    "hymba-1.5b": "hymba_1_5b",
    "chameleon-34b": "chameleon_34b",
    "granite-4.0-h-small": "granite_4_0_h_small",
}

ARCHS = tuple(_ARCH_MODULES)


def get_config(arch: str) -> ModelConfig:
    if arch not in _ARCH_MODULES:
        raise KeyError(f"unknown arch {arch!r}; available: {sorted(_ARCH_MODULES)}")
    mod = importlib.import_module(f".{_ARCH_MODULES[arch]}", __package__)
    return mod.CONFIG


def all_configs() -> dict[str, ModelConfig]:
    return {a: get_config(a) for a in ARCHS}


__all__ = ["ARCHS", "SHAPES", "ModelConfig", "ShapeConfig", "get_config",
           "all_configs", "reduced", "shape_applicable"]
