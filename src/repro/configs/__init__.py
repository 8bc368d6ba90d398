"""Architecture registry: the 10 assigned architectures + the paper's own
backbone, selectable via ``--arch <id>``."""
from __future__ import annotations

import importlib
from typing import Dict, List

from repro.models.config import ModelConfig

# arch id -> module name
ARCH_IDS = {
    "granite-34b": "granite_34b",
    "deepseek-v3-671b": "deepseek_v3_671b",
    "qwen3-0.6b": "qwen3_0p6b",
    "jamba-v0.1-52b": "jamba_v0p1_52b",
    "pixtral-12b": "pixtral_12b",
    "qwen1.5-110b": "qwen1p5_110b",
    "rwkv6-3b": "rwkv6_3b",
    "mixtral-8x22b": "mixtral_8x22b",
    "whisper-tiny": "whisper_tiny",
    "deepseek-7b": "deepseek_7b",
    # the paper's own backbone (not part of the assigned 10)
    "qwen3-1.7b": "qwen3_1p7b",
    # a DeepSeek-V3 block at RL post-training size
    "moonlight-16b-a3b": "moonlight_16b_a3b",
}

ASSIGNED = [k for k in ARCH_IDS if k not in ("qwen3-1.7b",
                                             "moonlight-16b-a3b")]


def get_config(arch_id: str) -> ModelConfig:
    if arch_id not in ARCH_IDS:
        raise KeyError(f"unknown arch '{arch_id}'; known: {sorted(ARCH_IDS)}")
    mod = importlib.import_module(f"repro.configs.{ARCH_IDS[arch_id]}")
    cfg = mod.config()
    cfg.validate()
    return cfg


def list_configs() -> List[str]:
    return sorted(ARCH_IDS)


def all_configs() -> Dict[str, ModelConfig]:
    return {k: get_config(k) for k in ARCH_IDS}
