"""Dense attention and a SwiGLU MLP (phi3-mini).

Causal attention: a query at position ``t`` attends to ``t + 1`` keys, so
over a sequence of ``S`` the average is ``(S + 1) / 2``; scores and the
weighted sum of values each cost ``2 * head_dim`` FLOPs per (query, key)
and head, i.e. ``2 * H * hd * (S + 1)`` per token and layer forward.
"""

from __future__ import annotations

BYTES = {"float32": 4, "bfloat16": 2}


def matmul_params(m: dict) -> int:
    L, D, V, F = m["n_layers"], m["d_model"], m["vocab_size"], m["d_ff"]
    a = m["attn"]
    q, kv = a["n_heads"] * a["head_dim"], a["n_kv_heads"] * a["head_dim"]
    return L * (2 * D * q + 2 * D * kv + 3 * D * F) + D * V


def mixing_flops_forward(m: dict, seq: int) -> float:
    a = m["attn"]
    return m["n_layers"] * 2.0 * a["n_heads"] * a["head_dim"] * (seq + 1)


def scope_work(m: dict, seq: int) -> dict:
    """``mlp``: the gate, up and down matmuls, 3x forward.  Its bytes are a
    lower bound: each pass reads the MLP's input and writes its output once
    (the backward pass their gradients), in the compute dtype; weights and
    the intermediate activations are left out."""
    L, D, F = m["n_layers"], m["d_model"], m["d_ff"]
    act = BYTES[m["compute_dtype"]]
    return {"mlp": (3.0 * 2.0 * L * 3 * D * F, 3.0 * L * 2 * D * act)}
