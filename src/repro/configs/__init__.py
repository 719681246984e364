"""Architecture config registry: ``--arch <id>`` resolution.

Each module defines the exact published config (with source citation) for
one assigned architecture; ``smoke_reduce`` produces the reduced same-family
variant used by CPU smoke tests.
"""

from __future__ import annotations

from repro.models import ModelConfig

from . import (deepseek_7b, deepseek_v2_lite, deepseek_v3, gemma2_2b, gemma_2b,
               internvl2_2b, jamba_1_5_large, musicgen_large, phi3_5_moe_42b,
               phi3_mini, rwkv6_7b)
from .common import smoke_reduce

_MODULES = (
    phi3_5_moe_42b, gemma_2b, rwkv6_7b, jamba_1_5_large, phi3_mini,
    musicgen_large, deepseek_v3, internvl2_2b, deepseek_7b, gemma2_2b,
    deepseek_v2_lite,
)

ARCH_IDS: tuple[str, ...] = tuple(m.ARCH_ID for m in _MODULES)
_BY_ID = {m.ARCH_ID: m for m in _MODULES}

# architecture family tags (from the assignment)
FAMILY = {
    "phi3.5-moe-42b-a6.6b": "moe",
    "gemma-2b": "dense",
    "rwkv6-7b": "ssm",
    "jamba-1.5-large-398b": "hybrid",
    "phi3-mini-3.8b": "dense",
    "musicgen-large": "audio",
    "deepseek-v3-671b": "moe",
    "deepseek-v2-lite-16b": "moe",
    "internvl2-2b": "vlm",
    "deepseek-7b": "dense",
    "gemma2-2b": "dense",
}

# archs allowed to run the long_500k decode shape (sub-quadratic path);
# see DESIGN.md §Arch-applicability for the skip rationale.
LONG_CONTEXT_OK = ("rwkv6-7b", "jamba-1.5-large-398b", "gemma2-2b")


def get_config(arch: str) -> ModelConfig:
    if arch not in _BY_ID:
        raise KeyError(f"unknown arch {arch!r}; known: {sorted(_BY_ID)}")
    return _BY_ID[arch].config()


def get_smoke_config(arch: str) -> ModelConfig:
    return smoke_reduce(get_config(arch))
