"""Assigned-architecture registry: ``--arch <id>`` resolves here.

Each module defines ``CONFIG`` (the exact published configuration) and
``REDUCED`` (a same-family miniature for CPU smoke tests).  The full
configs run on the chip (``chip_smoke.py`` serves ``qwen3-4b`` at its
published widths); the reduced ones run in the CPU test suite.
"""

from __future__ import annotations

from importlib import import_module

from ..models.config import ModelConfig

_ARCH_MODULES = {
    "mixtral-8x7b": "mixtral_8x7b",
    "deepseek-v2-lite-16b": "deepseek_v2_lite_16b",
    "stablelm-1.6b": "stablelm_1_6b",
    "command-r-plus-104b": "command_r_plus_104b",
    "qwen3-4b": "qwen3_4b",
    "gemma3-1b": "gemma3_1b",
    "whisper-tiny": "whisper_tiny",
    "rwkv6-3b": "rwkv6_3b",
    "internvl2-1b": "internvl2_1b",
    "hymba-1.5b": "hymba_1_5b",
}

ARCH_IDS = list(_ARCH_MODULES)


def get_config(arch: str, *, reduced: bool = False) -> ModelConfig:
    if arch not in _ARCH_MODULES:
        raise KeyError(f"unknown arch {arch!r}; choose from {ARCH_IDS}")
    mod = import_module(f".{_ARCH_MODULES[arch]}", __package__)
    return mod.REDUCED if reduced else mod.CONFIG
