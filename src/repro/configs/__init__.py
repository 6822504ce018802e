"""repro.configs — the 11 architectures.

Every architecture is selectable as ``--arch <id>`` in the launchers; each
also exposes a reduced ``smoke`` variant used by the CPU smoke tests.
"""
from __future__ import annotations

import importlib

from ..models import ModelConfig

ARCH_IDS = (
    "internvl2-2b",
    "mamba2-780m",
    "moonshot-v1-16b-a3b",
    "mixtral-8x22b",
    "hubert-xlarge",
    "minicpm-2b",
    "llama3.2-1b",
    "chatglm3-6b",
    "llama3-8b",
    "hymba-1.5b",
    "falcon-h1-34b",
)

_MODULES = {
    "internvl2-2b": "internvl2_2b",
    "mamba2-780m": "mamba2_780m",
    "moonshot-v1-16b-a3b": "moonshot_v1_16b_a3b",
    "mixtral-8x22b": "mixtral_8x22b",
    "hubert-xlarge": "hubert_xlarge",
    "minicpm-2b": "minicpm_2b",
    "llama3.2-1b": "llama3_2_1b",
    "chatglm3-6b": "chatglm3_6b",
    "llama3-8b": "llama3_8b",
    "hymba-1.5b": "hymba_1_5b",
    "falcon-h1-34b": "falcon_h1_34b",
}


def _module(arch: str):
    if arch not in _MODULES:
        raise KeyError(f"unknown arch {arch!r}; known: {list(_MODULES)}")
    return importlib.import_module(f".{_MODULES[arch]}", __package__)


def get_config(arch: str) -> ModelConfig:
    return _module(arch).config()


def get_smoke_config(arch: str) -> ModelConfig:
    return _module(arch).smoke_config()


__all__ = ["ARCH_IDS", "get_config", "get_smoke_config"]
