"""Architecture registry: ``get_config(name)`` / ``list_archs()``.

The 10 assigned architectures plus the TACC reference workload. Smoke
variants (tiny, same family) are exposed as ``get_config(name, smoke=True)``.
"""
from __future__ import annotations

from typing import Dict, List

from repro.configs.base import (LayerSpec, MLAConfig, MambaConfig, ModelConfig,
                                MoEConfig, ShapeConfig, SHAPES, XLSTMConfig,
                                YarnConfig, shape_applicable)

from repro.configs import (starcoder2_15b, internlm2_1_8b, llama3_405b,
                           command_r_plus_104b, internvl2_2b, xlstm_125m,
                           qwen2_moe_a2_7b, deepseek_v2_236b,
                           deepseek_v2_lite,
                           jamba_1_5_large_398b, musicgen_medium, tacc_100m)

_MODULES = {
    "starcoder2-15b": starcoder2_15b,
    "internlm2-1.8b": internlm2_1_8b,
    "llama3-405b": llama3_405b,
    "command-r-plus-104b": command_r_plus_104b,
    "internvl2-2b": internvl2_2b,
    "xlstm-125m": xlstm_125m,
    "qwen2-moe-a2.7b": qwen2_moe_a2_7b,
    "deepseek-v2-236b": deepseek_v2_236b,
    "deepseek-v2-lite": deepseek_v2_lite,
    "deepseek-v2-lite-ep8": deepseek_v2_lite.EP8,
    "jamba-1.5-large-398b": jamba_1_5_large_398b,
    "musicgen-medium": musicgen_medium,
    "tacc-100m": tacc_100m,
}

ASSIGNED_ARCHS = [k for k in _MODULES if k != "tacc-100m"]


def list_archs() -> List[str]:
    return list(_MODULES)


def get_config(name: str, smoke: bool = False) -> ModelConfig:
    if name not in _MODULES:
        raise KeyError(f"unknown arch '{name}'; known: {list(_MODULES)}")
    mod = _MODULES[name]
    return mod.SMOKE if smoke else mod.CONFIG
