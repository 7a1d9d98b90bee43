"""Architecture registry: the 10 assigned configs and the served
granite-4.0-h-micro (+ the paper's Jacobi experiment config) selectable
via ``--arch <id>``."""
from __future__ import annotations

import importlib

from repro.models.config import ModelConfig

_MODULES = {
    "whisper-base": "whisper_base",
    "qwen2-1.5b": "qwen2_1_5b",
    "deepseek-coder-33b": "deepseek_coder_33b",
    "gemma3-4b": "gemma3_4b",
    "llama3-405b": "llama3_405b",
    "zamba2-1.2b": "zamba2_1_2b",
    "mixtral-8x7b": "mixtral_8x7b",
    "qwen2-moe-a2.7b": "qwen2_moe_a2_7b",
    "chameleon-34b": "chameleon_34b",
    "mamba2-370m": "mamba2_370m",
    "granite-4.0-h-micro": "granite_4_0_h_micro",
}

ARCHS = tuple(_MODULES)


def _mod(arch: str):
    if arch not in _MODULES:
        raise KeyError(f"unknown arch {arch!r}; choose from {list(_MODULES)}")
    return importlib.import_module(f"repro.configs.{_MODULES[arch]}")


def get_config(arch: str) -> ModelConfig:
    return _mod(arch).CONFIG


def get_smoke_config(arch: str) -> ModelConfig:
    return _mod(arch).SMOKE

