"""Architecture registry: one module per assigned architecture."""
from __future__ import annotations

import importlib
from typing import Dict, List

from repro.configs.base import ModelConfig, SHAPES, ShapeConfig, shape_applicable

ARCH_IDS = [
    "phi35_moe",
    "mixtral_8x22b",
    "minitron_4b",
    "qwen2_7b",
    "olmo_1b",
    "granite_8b",
    "recurrentgemma_2b",
    "internvl2_76b",
    "mamba2_1_3b",
    "whisper_medium",
    "deepseek_v2_lite",
]

_ALIASES = {
    "phi3.5-moe-42b-a6.6b": "phi35_moe",
    "mixtral-8x22b": "mixtral_8x22b",
    "minitron-4b": "minitron_4b",
    "qwen2-7b": "qwen2_7b",
    "olmo-1b": "olmo_1b",
    "granite-8b": "granite_8b",
    "recurrentgemma-2b": "recurrentgemma_2b",
    "internvl2-76b": "internvl2_76b",
    "mamba2-1.3b": "mamba2_1_3b",
    "whisper-medium": "whisper_medium",
    "deepseek-v2-lite": "deepseek_v2_lite",
}


def get_config(arch: str) -> ModelConfig:
    mod_name = _ALIASES.get(arch, arch.replace("-", "_").replace(".", "_"))
    mod = importlib.import_module(f"repro.configs.{mod_name}")
    return mod.CONFIG


def get_smoke_config(arch: str) -> ModelConfig:
    mod_name = _ALIASES.get(arch, arch.replace("-", "_").replace(".", "_"))
    mod = importlib.import_module(f"repro.configs.{mod_name}")
    return mod.smoke_config()


def all_archs() -> List[str]:
    return list(ARCH_IDS)
