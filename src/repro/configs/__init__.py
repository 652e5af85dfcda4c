"""Architecture registry: the 10 assigned architectures, the paper's own
model and the benchmark's DeepSeek-V2-Lite share. ``get_config(name)`` accepts hyphen or underscore spellings;
``--arch <id>`` in the launchers resolves through this registry.
"""

from __future__ import annotations

from typing import Dict, List

from ..models.config import ModelConfig

from .dbrx_132b import CONFIG as DBRX_132B
from .musicgen_medium import CONFIG as MUSICGEN_MEDIUM
from .qwen2_vl_7b import CONFIG as QWEN2_VL_7B
from .gemma2_27b import CONFIG as GEMMA2_27B
from .zamba2_7b import CONFIG as ZAMBA2_7B
from .granite_moe_3b_a800m import CONFIG as GRANITE_MOE_3B_A800M
from .qwen2_0_5b import CONFIG as QWEN2_0_5B
from .nemotron_4_340b import CONFIG as NEMOTRON_4_340B
from .mamba2_1_3b import CONFIG as MAMBA2_1_3B
from .chatglm3_6b import CONFIG as CHATGLM3_6B
from .paper_qwen15_0_5b import CONFIG as PAPER_QWEN15_0_5B
from .deepseek_v2_lite import CONFIG as DEEPSEEK_V2_LITE

ASSIGNED: Dict[str, ModelConfig] = {
    c.name: c
    for c in [
        DBRX_132B,
        MUSICGEN_MEDIUM,
        QWEN2_VL_7B,
        GEMMA2_27B,
        ZAMBA2_7B,
        GRANITE_MOE_3B_A800M,
        QWEN2_0_5B,
        NEMOTRON_4_340B,
        MAMBA2_1_3B,
        CHATGLM3_6B,
    ]
}

ALL: Dict[str, ModelConfig] = {
    **ASSIGNED,
    PAPER_QWEN15_0_5B.name: PAPER_QWEN15_0_5B,
    DEEPSEEK_V2_LITE.name: DEEPSEEK_V2_LITE,
}


def get_config(name: str) -> ModelConfig:
    key = name.replace("_", "-").lower()
    for k, v in ALL.items():
        if k.lower() == key:
            return v
    raise KeyError(f"unknown arch '{name}'; known: {sorted(ALL)}")


def list_archs() -> List[str]:
    return sorted(ASSIGNED)


__all__ = ["ASSIGNED", "ALL", "get_config", "list_archs"]
