"""Multi-head latent attention and DeepSeekMoE (DeepSeek-V2-Lite).

Every attention layer (the ``first_k_dense`` dense ones and the
``n_layers`` expert ones) projects ``q = x Wq``, ``[c, k_r] = x Wkv_a``,
``k = c Wk_b``, ``v = c Wv_b`` and ``Wo``.  Causal attention: a query at
position ``t`` attends to ``t + 1`` keys, ``(S + 1) / 2`` on average; scores
cost ``2 (qk_nope + qk_rope)`` and the weighted sum of values ``2 v_head_dim``
FLOPs per (query, key) and head.

An expert layer's router scores every routed expert; the shared experts run
on every token; of the routed experts, a token's ``top_k`` choices fall on
the ``n_experts`` held here with probability ``n_experts / n_routed``, so
the held experts do ``top_k * n_experts / n_routed`` experts' work per token
on average.
"""

from __future__ import annotations

BYTES = {"float32": 4, "bfloat16": 2}


def _mla_params(m: dict) -> int:
    D, a = m["d_model"], m["attn"]
    ml, H = a["mla"], a["n_heads"]
    qk = ml["qk_nope_dim"] + ml["qk_rope_dim"]
    R = ml["kv_lora_rank"]
    return (D * H * qk + D * (R + ml["qk_rope_dim"])
            + R * H * (ml["qk_nope_dim"] + ml["v_head_dim"])
            + H * ml["v_head_dim"] * D)


def _attn_layers(m: dict) -> int:
    return m["first_k_dense"] + m["n_layers"]


def matmul_params(m: dict) -> float:
    D, e = m["d_model"], m["moe"]
    expert = 3 * D * e["d_ff"]
    moe = (D * e["n_routed"] + e["n_shared_experts"] * expert
           + e["top_k"] * e["n_experts"] * expert / e["n_routed"])
    return (_attn_layers(m) * _mla_params(m)
            + m["first_k_dense"] * 3 * D * m["d_ff"]
            + m["n_layers"] * moe + D * m["vocab_size"])


def mixing_flops_forward(m: dict, seq: int) -> float:
    a = m["attn"]
    ml = a["mla"]
    per_key = 2.0 * a["n_heads"] * (ml["qk_nope_dim"] + ml["qk_rope_dim"]
                                    + ml["v_head_dim"])
    return _attn_layers(m) * per_key * (seq + 1) / 2


def scope_work(m: dict, seq: int) -> dict:
    """``mla``: the latent attention's projections and its causal scores
    and values, 3x forward.  Its bytes are a lower bound: each pass reads
    the layer's input and writes its output once, in the compute dtype."""
    L = _attn_layers(m)
    act = BYTES[m["compute_dtype"]]
    flops = 3.0 * (2.0 * L * _mla_params(m) + mixing_flops_forward(m, seq))
    return {"mla": (flops, 3.0 * L * 2 * m["d_model"] * act)}
