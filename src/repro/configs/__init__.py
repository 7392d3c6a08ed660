"""Assigned-architecture registry: one module per architecture, each
exporting ``FULL`` (the exact assigned dims) and ``REDUCED`` (a same-family
miniature for CPU smoke tests), plus optional shape-skip notes.

Input-shape cells (applied per arch; see launch/shapes.py):
    train_4k     seq 4096  x global_batch 256   (train_step)
    prefill_32k  seq 32768 x global_batch 32    (prefill)
    decode_32k   seq 32768 x global_batch 128   (serve_step, 1 new token)
    long_500k    seq 524288 x global_batch 1    (serve_step, sub-quadratic
                                                 archs only)
"""

from __future__ import annotations

import importlib
from typing import Dict, List

from repro.models.config import ModelConfig

ARCHS: List[str] = [
    "gemma3_12b",
    "internlm2_1_8b",
    "qwen2_0_5b",
    "qwen1_5_32b",
    "deepseek_v2_lite_16b",
    "mixtral_8x7b",
    "qwen2_vl_7b",
    "mamba2_2_7b",
    "zamba2_7b",
    "seamless_m4t_large_v2",
    "granite_4_0_h_micro",
]

# canonical ids as given in the assignment -> module names
ALIASES = {
    "gemma3-12b": "gemma3_12b",
    "internlm2-1.8b": "internlm2_1_8b",
    "qwen2-0.5b": "qwen2_0_5b",
    "qwen1.5-32b": "qwen1_5_32b",
    "deepseek-v2-lite-16b": "deepseek_v2_lite_16b",
    "mixtral-8x7b": "mixtral_8x7b",
    "qwen2-vl-7b": "qwen2_vl_7b",
    "mamba2-2.7b": "mamba2_2_7b",
    "zamba2-7b": "zamba2_7b",
    "seamless-m4t-large-v2": "seamless_m4t_large_v2",
    "granite-4.0-h-micro": "granite_4_0_h_micro",
}


def _module(name: str):
    mod = ALIASES.get(name, name).replace("-", "_").replace(".", "_")
    return importlib.import_module(f"repro.configs.{mod}")


def get_config(name: str) -> ModelConfig:
    return _module(name).FULL


def get_reduced(name: str) -> ModelConfig:
    return _module(name).REDUCED


def shape_skips(name: str) -> Dict[str, str]:
    """shape id -> reason, for cells this arch skips."""
    return getattr(_module(name), "SKIP_SHAPES", {})


def all_configs() -> Dict[str, ModelConfig]:
    return {a: get_config(a) for a in ARCHS}
