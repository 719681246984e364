"""Model FLOPs per trained token, from a configuration's sizes.

Training counts 3x the forward pass (forward, then backward for inputs
and for weights); recomputation under remat is not counted.  A matmul
with an ``n``-parameter weight costs ``2n`` FLOPs per token forward.
Causal attention: a query at position ``t`` attends to ``t + 1`` keys, so
over a sequence of ``S`` the average is ``(S + 1) / 2``; scores and the
weighted sum of values each cost ``2 * head_dim`` FLOPs per (query, key)
and head, i.e. ``2 * H * hd * (S + 1)`` per token and layer forward.  The
RWKV-6 recurrence per token and head reads ``r (S + u k^T v)`` and updates
``S`` (``head_dim^2`` entries each, a multiply and an add):
``4 * D * head_dim`` per token and layer forward.  Element-wise work
(norms, activations, token shift, the lerp) is not counted.
"""

from __future__ import annotations


def matmul_params(family: str, m: dict) -> int:
    """Parameters that take part in a matmul per token (head included,
    embedding lookup not)."""
    L, D, V, F = m["n_layers"], m["d_model"], m["vocab_size"], m["d_ff"]
    if family == "dense_swiglu":
        a = m["attn"]
        q, kv = a["n_heads"] * a["head_dim"], a["n_kv_heads"] * a["head_dim"]
        layer = 2 * D * q + 2 * D * kv + 3 * D * F
    elif family == "rwkv6":
        r = m["rwkv"]
        layer = (10 * D * r["mix_lora"] + 2 * D * r["decay_lora"]
                 + 5 * D * D + 2 * D * F + D * D)
    else:
        raise ValueError(f"no FLOP count for model family {family!r}")
    return L * layer + D * V


def mixing_flops_forward(family: str, m: dict, seq: int) -> float:
    """Per-token forward FLOPs of sequence mixing that has no weight."""
    L = m["n_layers"]
    if family == "dense_swiglu":
        a = m["attn"]
        return L * 2.0 * a["n_heads"] * a["head_dim"] * (seq + 1)
    if family == "rwkv6":
        return L * 4.0 * m["d_model"] * m["rwkv"]["head_dim"]
    raise ValueError(f"no FLOP count for model family {family!r}")


def train_flops_per_token(family: str, m: dict, seq: int) -> float:
    return 3.0 * (2.0 * matmul_params(family, m)
                  + mixing_flops_forward(family, m, seq))
