"""Config registry: ``--arch <id>`` resolution for launchers and tests."""
from __future__ import annotations

from typing import Dict, Tuple

from repro.configs.base import (  # noqa: F401
    EncDecConfig,
    HybridConfig,
    MLAConfig,
    ModelConfig,
    MoEConfig,
    SSMConfig,
    VLMConfig,
)

from repro.configs import (
    chameleon_34b,
    deepseek_v2_236b,
    hstu,
    llama3_2_1b,
    llama3_405b,
    mamba2_130m,
    qwen2_5_3b,
    qwen3_moe_30b_a3b,
    recurrentgemma_2b,
    seamless_m4t,
    whisper_base,
    yi_34b,
)

_MODULES = {
    "deepseek-v2-236b": deepseek_v2_236b,
    "yi-34b": yi_34b,
    "qwen3-moe-30b-a3b": qwen3_moe_30b_a3b,
    "chameleon-34b": chameleon_34b,
    "llama3.2-1b": llama3_2_1b,
    "whisper-base": whisper_base,
    "mamba2-130m": mamba2_130m,
    "llama3-405b": llama3_405b,
    "recurrentgemma-2b": recurrentgemma_2b,
    "qwen2.5-3b": qwen2_5_3b,
    "hstu": hstu,
    "seamless-m4t": seamless_m4t,
}

_EXTRAS = ("hstu", "seamless-m4t")  # paper-own, outside the assigned table

#: The ten assigned architectures (HSTU/Seamless are paper-own extras).
ASSIGNED_ARCHS: Tuple[str, ...] = tuple(k for k in _MODULES if k not in _EXTRAS)

CONFIGS: Dict[str, ModelConfig] = {k: m.CONFIG for k, m in _MODULES.items()}
SMOKE_CONFIGS: Dict[str, ModelConfig] = {k: m.SMOKE for k, m in _MODULES.items()}


def get_config(arch: str) -> ModelConfig:
    if arch not in CONFIGS:
        raise KeyError(f"unknown arch {arch!r}; choose from {sorted(CONFIGS)}")
    return CONFIGS[arch]


def get_smoke_config(arch: str) -> ModelConfig:
    return SMOKE_CONFIGS[arch]
