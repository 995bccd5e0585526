"""Plugin registries with deterministic initialization.

Parity target: reference ``src/llmtrain/registry/__init__.py`` — registries
are populated by a fixed import list (not entry-point discovery), each plugin
module self-registering via decorator at import time (:7-20).
"""

from __future__ import annotations

import importlib

from .data import available_data_modules, get_data_module, register_data_module
from .models import (
    RegistryError,
    available_model_adapters,
    get_model_adapter,
    register_model,
)

_PLUGIN_MODULES = (
    "llmtrain_tpu.models.dummy_gpt",
    "llmtrain_tpu.models.gpt",
    "llmtrain_tpu.models.gpt_moe",
    "llmtrain_tpu.models.gpt_pipeline",
    "llmtrain_tpu.models.llama",
    "llmtrain_tpu.models.qwen2",
    "llmtrain_tpu.models.gemma",
    "llmtrain_tpu.models.falcon_h1",
    "llmtrain_tpu.models.latent_moe",
    "llmtrain_tpu.models.indexed_moe",
    "llmtrain_tpu.models.windowed_moe",
    "llmtrain_tpu.data.dummy_text",
    "llmtrain_tpu.data.hf_text",
    "llmtrain_tpu.data.local_text",
    "llmtrain_tpu.data.mixed_text",
)


def initialize_registries() -> None:
    """Import every built-in plugin module exactly once."""
    for module in _PLUGIN_MODULES:
        importlib.import_module(module)


__all__ = [
    "RegistryError",
    "available_data_modules",
    "available_model_adapters",
    "get_data_module",
    "get_model_adapter",
    "initialize_registries",
    "register_data_module",
    "register_model",
]
