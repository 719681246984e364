"""RWKV-6 (Finch): token-shift LoRAs, time mix and channel mix.

The recurrence per token and head reads ``r (S + u k^T v)`` and updates
``S`` (``head_dim^2`` entries each, a multiply and an add):
``4 * D * head_dim`` per token and layer forward.  Token shift and the
lerp are element-wise and not counted.
"""

from __future__ import annotations


def matmul_params(m: dict) -> int:
    L, D, V, F = m["n_layers"], m["d_model"], m["vocab_size"], m["d_ff"]
    r = m["rwkv"]
    layer = (10 * D * r["mix_lora"] + 2 * D * r["decay_lora"]
             + 5 * D * D + 2 * D * F + D * D)
    return L * layer + D * V


def mixing_flops_forward(m: dict, seq: int) -> float:
    return m["n_layers"] * 4.0 * m["d_model"] * m["rwkv"]["head_dim"]
