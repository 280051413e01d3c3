"""Architecture registry: ``get_config("<arch-id>")``."""
from __future__ import annotations

import importlib

from repro.configs.base import (BlockSpec, MLAConfig, ModelConfig, MoEConfig,
                                SSMConfig, Stage, reduce_config)

# arch id -> module name
_REGISTRY = {
    "gemma3-27b": "gemma3_27b",
    "minicpm-2b": "minicpm_2b",
    "gemma3-12b": "gemma3_12b",
    "qwen3-32b": "qwen3_32b",
    "jamba-v0.1-52b": "jamba_52b",
    "mamba2-1.3b": "mamba2_1p3b",
    "deepseek-v2-lite-16b": "deepseek_v2_lite",
    "mixtral-8x22b": "mixtral_8x22b",
    "internvl2-1b": "internvl2_1b",
    "musicgen-medium": "musicgen_medium",
    # the paper's own testbed backends
    "llama3.1-8b": "llama31_8b",
    "qwen2.5-14b": "qwen25_14b",
}

ALL_ARCHS = tuple(_REGISTRY)


def get_config(name: str) -> ModelConfig:
    if name not in _REGISTRY:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(_REGISTRY)}")
    mod = importlib.import_module(f"repro.configs.{_REGISTRY[name]}")
    return mod.CONFIG


__all__ = [
    "ALL_ARCHS", "BlockSpec", "MLAConfig", "ModelConfig", "MoEConfig",
    "SSMConfig", "Stage", "get_config", "reduce_config",
]
