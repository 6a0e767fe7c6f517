"""Architecture registry: ``get_config(name)`` / ``--arch <id>``.

Ten assigned architectures, DeepSeek-V2-Lite, and the paper's own two
(Gemma2-2B, Mistral-7B).
Each module exposes ``config()`` (full published config) and
``smoke_config()`` (reduced same-family config for CPU smoke tests).
"""

from __future__ import annotations

import importlib

from repro.config import ModelConfig

ARCH_IDS = (
    "whisper-medium",
    "smollm-360m",
    "mistral-nemo-12b",
    "smollm-135m",
    "stablelm-1.6b",
    "granite-moe-3b-a800m",
    "deepseek-v2-236b",
    "deepseek-v2-lite",
    "mamba2-370m",
    "qwen2-vl-2b",
    "jamba-1.5-large-398b",
    # the paper's own models
    "gemma2-2b",
    "mistral-7b",
)

_MODULES = {name: "repro.configs." + name.replace("-", "_").replace(".", "_")
            for name in ARCH_IDS}


def get_config(name: str) -> ModelConfig:
    if name not in _MODULES:
        raise KeyError(f"unknown arch {name!r}; choose from {ARCH_IDS}")
    return importlib.import_module(_MODULES[name]).config()


def get_smoke_config(name: str) -> ModelConfig:
    return importlib.import_module(_MODULES[name]).smoke_config()
