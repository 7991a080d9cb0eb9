"""Architecture configs: one module per assigned architecture."""

from .base import (
    ModelConfig,
    ShapeConfig,
    SHAPES,
    all_configs,
    get_config,
    register,
    shape_applicable,
    smoke_config,
)

_LOADED = False

ARCH_MODULES = [
    "qwen3_moe_30b_a3b",
    "phi35_moe_42b_a66b",
    "gemma_2b",
    "llama3_405b",
    "yi_6b",
    "phi4_mini_38b",
    "rwkv6_16b",
    "internvl2_1b",
    "recurrentgemma_2b",
    "whisper_large_v3",
    "mellum2_12b_a2_5b",
]


def _load_all() -> None:
    global _LOADED
    if _LOADED:
        return
    import importlib

    for mod in ARCH_MODULES:
        importlib.import_module(f"{__name__}.{mod}")
    _LOADED = True


ARCH_IDS = [
    "qwen3-moe-30b-a3b",
    "phi3.5-moe-42b-a6.6b",
    "gemma-2b",
    "llama3-405b",
    "yi-6b",
    "phi4-mini-3.8b",
    "rwkv6-1.6b",
    "internvl2-1b",
    "recurrentgemma-2b",
    "whisper-large-v3",
    "mellum2-12b-a2.5b",
    "mellum2-12b-a2.5b-ep4",
]
