"""Plain decoder with multi-head latent attention and a DeepSeekMoE layer
(DeepSeek-V2-Lite, https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite).

Per layer: ``x += Wo attn(n1(x))`` then ``x += ffn(n2(x))``, with RMSNorms
``n1, n2``.  The first ``first_k_dense`` layers have a SwiGLU ``ffn`` of
``d_ff``; the ``n_layers`` layers after them a mixture of experts.  Then a
final RMSNorm and an untied head over the vocabulary held here.

Attention (MLA without a query bottleneck): ``q = x Wq`` per head of
``qk_nope + qk_rope`` channels; ``[c, k_r] = x Wkv_a``, ``c`` RMS-normalised
(eps 1e-6, the released ``kv_a_layernorm``), ``k_nope = c Wk_b`` and
``v = c Wv_b`` per head, ``k_r`` one rope key shared by every head.  Rope
turns the last ``qk_rope`` channels of ``q`` and ``k_r`` by YaRN's
frequencies: ``b^(-2i/d)`` divided by ``factor`` below the ramp, kept above
it, linear between channel pairs ``floor(corr(beta_fast))`` and
``ceil(corr(beta_slow))`` with ``corr(r) = d ln(orig / (2 pi r)) / (2 ln
b)``; cos and sin times ``mscale(mscale) / mscale(mscale_all_dim)``, where
``mscale(m) = 0.1 m ln(factor) + 1``.  Scores ``q . k`` times
``(qk_nope + qk_rope)^-0.5 mscale(mscale_all_dim)^2``, a full causal softmax
in float32, one head at a time under ``jax.checkpoint``.

Experts: router logits over all ``n_routed`` experts, softmax, the top-k
(weights left as the probabilities where ``norm_topk_prob`` is false).  Of
the ``n_experts`` experts held here (from ``first_expert`` on), each SwiGLU
expert of ``moe.d_ff`` runs on every token, weighted by that token's routing
weight (zero where the expert was not chosen); what the experts held
elsewhere would add is left out.  The shared experts are one SwiGLU of
``moe.d_ff * n_shared_experts`` on every token.  The balance loss is the
released ``seq_aux``: per sequence of ``S`` tokens ``f_i = E / (K S)
#{choices of i}``, ``P_i`` the mean score of ``i``, and ``alpha sum_i f_i
P_i`` added to the loss for each expert layer.  Every layer runs under
``jax.checkpoint``; every matmul takes its operands in ``precision``
(``common.operand``).

Departures from the released model:

* rope rotates the two halves of the rope channels (``x1 cos - x2 sin,
  x2 cos + x1 sin``); the released checkpoint stores those channels
  interleaved, a fixed permutation of the rope columns of ``Wq`` and
  ``Wkv_a`` that makes no difference on seeded random weights;
* routing is dropless: the paper drops tokens past a device-level capacity
  of 1.0, the released modelling code drops none;
* no device-level or communication balance loss (the released modelling
  code has neither).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from .common import dot, next_token_ce, operand, rmsnorm

KV_NORM_EPS = 1e-6


def init(draw, m: dict):
    L, D, V, F = m["n_layers"], m["d_model"], m["vocab_size"], m["d_ff"]
    a, e = m["attn"], m["moe"]
    ml, H = a["mla"], a["n_heads"]
    nope, rope, vd, R = (ml["qk_nope_dim"], ml["qk_rope_dim"],
                         ml["v_head_dim"], ml["kv_lora_rank"])

    def attn(*lead):
        return {"wq": draw.fan_in((*lead, D, H * (nope + rope))),
                "wkv_a": draw.fan_in((*lead, D, R + rope)),
                "kv_norm": {"scale": draw.ones((*lead, R))},
                "wk_b": draw.fan_in((*lead, R, H * nope)),
                "wv_b": draw.fan_in((*lead, R, H * vd)),
                "wo": draw.fan_in((*lead, H * vd, D))}

    def swiglu(width, *lead):
        return {"gate": draw.fan_in((*lead, D, width)),
                "up": draw.fan_in((*lead, D, width)),
                "down": draw.fan_in((*lead, width, D))}

    dense = tuple({"norm1": {"scale": draw.ones((D,))}, "attn": attn(),
                   "norm2": {"scale": draw.ones((D,))}, "mlp": swiglu(F)}
                  for _ in range(m["first_k_dense"]))
    layer = {"norm1": {"scale": draw.ones((L, D))}, "attn": attn(L),
             "norm2": {"scale": draw.ones((L, D))},
             "moe": {"router": draw.fan_in((L, D, e["n_routed"])),
                     "experts": swiglu(e["d_ff"], L, e["n_experts"]),
                     "shared": swiglu(e["d_ff"] * e["n_shared_experts"], L)}}
    return {"embed": draw.normal((V, D), 0.02),
            "dense_prefix": dense,
            "periods": {"layers": (layer,)},
            "final_norm": {"scale": draw.ones((D,))},
            "head": draw.fan_in((D, V))}


def yarn(m: dict, S: int):
    """YaRN cos and sin tables (S, qk_rope / 2) and the softmax scale."""
    a = m["attn"]
    y, d, b = a["rope_scaling"], a["mla"]["qk_rope_dim"], a["rope_theta"]

    def corr(rot):
        return (d * math.log(y["original_max_position_embeddings"]
                             / (2 * math.pi * rot)) / (2 * math.log(b)))

    def mscale(x):
        return 0.1 * x * math.log(y["factor"]) + 1.0

    low = max(math.floor(corr(y["beta_fast"])), 0)
    high = min(math.ceil(corr(y["beta_slow"])), d - 1)
    i = jnp.arange(d // 2, dtype=jnp.float32)
    ramp = jnp.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    base = 1.0 / b ** (2 * i / d)
    inv_freq = base / y["factor"] * ramp + base * (1 - ramp)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv_freq
    c = mscale(y["mscale"]) / mscale(y["mscale_all_dim"])
    qk = a["mla"]["qk_nope_dim"] + d
    scale = qk ** -0.5 * mscale(y["mscale_all_dim"]) ** 2
    return jnp.cos(ang) * c, jnp.sin(ang) * c, scale


def _rot(x, cos, sin):
    """Split-half rope of x (B, S, H, d) by tables (S, d/2)."""
    d2 = x.shape[-1] // 2
    cos, sin = cos[:, None], sin[:, None]
    x1, x2 = x[..., :d2], x[..., d2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(p, x, m: dict, prec):
    a = m["attn"]
    ml, H = a["mla"], a["n_heads"]
    nope, rope, vd, R = (ml["qk_nope_dim"], ml["qk_rope_dim"],
                         ml["v_head_dim"], ml["kv_lora_rank"])
    B, S, _ = x.shape
    cos, sin, scale = yarn(m, S)
    q = dot(x, p["wq"], prec).reshape(B, S, H, nope + rope)
    q = jnp.concatenate([q[..., :nope], _rot(q[..., nope:], cos, sin)], -1)
    kv = dot(x, p["wkv_a"], prec)
    c = rmsnorm(p["kv_norm"]["scale"], kv[..., :R], KV_NORM_EPS)
    k_rope = _rot(kv[..., None, R:], cos, sin)                 # (B, S, 1, rope)
    k = jnp.concatenate([dot(c, p["wk_b"], prec).reshape(B, S, H, nope),
                         jnp.broadcast_to(k_rope, (B, S, H, rope))], -1)
    v = dot(c, p["wv_b"], prec).reshape(B, S, H, vd)
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]

    def head(qkv):
        qh, kh, vh = qkv                                       # (B, S, d)
        s = jnp.einsum("bqd,bkd->bqk", operand(qh, prec), operand(kh, prec),
                       preferred_element_type=jnp.float32) * scale
        w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return jnp.einsum("bqk,bkd->bqd", operand(w, prec), operand(vh, prec))

    per_head = (t.transpose(2, 0, 1, 3) for t in (q, k, v))
    o = lax.map(jax.checkpoint(head), tuple(per_head))        # (H, B, S, vd)
    o = o.transpose(1, 2, 0, 3).reshape(B, S, H * vd)
    return dot(o, p["wo"], prec)


def _swiglu(p, x, prec):
    return dot(jax.nn.silu(dot(x, p["gate"], prec)) * dot(x, p["up"], prec),
               p["down"], prec)


def _experts(p, x, m: dict, prec):
    """The held experts' part of the layer, the shared experts, and the
    layer's ``seq_aux`` loss (mean over the sequences of ``x``)."""
    e = m["moe"]
    B, S, D = x.shape
    E, K = e["n_routed"], e["top_k"]
    scores = jax.nn.softmax(dot(x, p["router"], prec).astype(jnp.float32), -1)
    top_w, top_e = lax.top_k(scores, K)                        # (B, S, K)
    if e["norm_topk_prob"]:
        top_w = top_w / jnp.sum(top_w, -1, keepdims=True)
    chosen = jax.nn.one_hot(top_e, E, dtype=jnp.float32)       # (B, S, K, E)
    weight = jnp.einsum("bsk,bske->bse", top_w, chosen)
    first = e["first_expert"]
    weight = weight[..., first:first + e["n_experts"]]         # held experts
    ex = p["experts"]
    h = (jax.nn.silu(dot(x[:, None], ex["gate"][None], prec))
         * dot(x[:, None], ex["up"][None], prec))              # (B, E, S, F)
    y = dot(h, ex["down"][None], prec)                         # (B, E, S, D)
    out = jnp.einsum("bse,besd->bsd", weight, y) + _swiglu(p["shared"], x, prec)
    f = chosen.sum((1, 2)) * (E / (K * S))                     # (B, E)
    aux = e["aux_loss_weight"] * jnp.mean(jnp.sum(f * scores.mean(1), -1))
    return out, aux


def _layer(p, x, m: dict, prec, moe: bool):
    eps = m["norm_eps"]
    x = x + _attention(p["attn"], rmsnorm(p["norm1"]["scale"], x, eps), m,
                       prec).astype(x.dtype)
    h = rmsnorm(p["norm2"]["scale"], x, eps)
    if not moe:
        return x + _swiglu(p["mlp"], h, prec).astype(x.dtype), 0.0
    out, aux = _experts(p["moe"], h, m, prec)
    return x + out.astype(x.dtype), aux


def loss(params, tokens, m: dict, precision: str = "float32"):
    """Mean next-token cross entropy of ``tokens`` (B, S), plus the expert
    layers' balance losses."""
    x = params["embed"][tokens]
    dense = jax.checkpoint(lambda p, x: _layer(p, x, m, precision, False)[0])
    for p in params["dense_prefix"]:
        x = dense(p, x)

    def body(x, p):
        return _layer(p, x, m, precision, True)

    x, aux = lax.scan(jax.checkpoint(body), x, params["periods"]["layers"][0])
    h = rmsnorm(params["final_norm"]["scale"], x, m["norm_eps"])
    return next_token_ce(h, params["head"], tokens, precision) + jnp.sum(aux)
