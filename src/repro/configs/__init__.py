"""Architecture registry: ``get_config(name)`` / ``--arch <id>``."""
from __future__ import annotations

import importlib

from .base import SHAPES, ModelConfig, ShapeConfig, YarnScaling

_ARCHS = {
    "granite-moe-1b-a400m": "granite_moe_1b_a400m",
    "deepseek-v2-lite-16b": "deepseek_v2_lite_16b",
    "zamba2-1.2b": "zamba2_1p2b",
    "minicpm3-4b": "minicpm3_4b",
    "gemma3-1b": "gemma3_1b",
    "gemma2-2b": "gemma2_2b",
    "mistral-large-123b": "mistral_large_123b",
    "mamba2-1.3b": "mamba2_1p3b",
    "whisper-tiny": "whisper_tiny",
    "pixtral-12b": "pixtral_12b",
}

ARCH_NAMES = tuple(_ARCHS)


def get_config(name: str) -> ModelConfig:
    if name not in _ARCHS:
        raise KeyError(f"unknown arch {name!r}; available: {ARCH_NAMES}")
    mod = importlib.import_module(f".{_ARCHS[name]}", __package__)
    return mod.CONFIG


def all_configs():
    return {name: get_config(name) for name in ARCH_NAMES}


__all__ = ["ARCH_NAMES", "SHAPES", "ModelConfig", "ShapeConfig", "YarnScaling",
           "get_config", "all_configs"]
