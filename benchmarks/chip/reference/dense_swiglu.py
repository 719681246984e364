"""Plain decoder with multi-head causal attention and a SwiGLU MLP
(the Phi-3-mini block, arXiv:2404.14219).

Per layer: ``x += Wo attn(rope(Wq n1(x)), rope(Wk n1(x)), Wv n1(x))`` then
``x += Wdown (silu(Wgate n2(x)) * Wup n2(x))``, with RMSNorms ``n1, n2``;
then a final RMSNorm and an untied head.  RoPE rotates the two halves of
each head (``x1 cos - x2 sin, x2 cos + x1 sin``).  Attention is computed
whole, scores and softmax in float32, one layer at a time under
``jax.checkpoint``.  Every matmul takes its operands in ``precision``
(``common.operand``: float32 for the reference, int8 for the control).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import dot, next_token_ce, operand, rmsnorm


def init(draw, m: dict):
    L, D, V, F = m["n_layers"], m["d_model"], m["vocab_size"], m["d_ff"]
    a = m["attn"]
    q, kv = a["n_heads"] * a["head_dim"], a["n_kv_heads"] * a["head_dim"]
    layer = {
        "norm1": {"scale": draw.ones((L, D))},
        "attn": {"wq": draw.fan_in((L, D, q)), "wk": draw.fan_in((L, D, kv)),
                 "wv": draw.fan_in((L, D, kv)), "wo": draw.fan_in((L, q, D))},
        "norm2": {"scale": draw.ones((L, D))},
        "mlp": {"gate": draw.fan_in((L, D, F)), "up": draw.fan_in((L, D, F)),
                "down": draw.fan_in((L, F, D))},
    }
    return {"embed": draw.normal((V, D), 0.02),
            "periods": {"layers": (layer,)},
            "final_norm": {"scale": draw.ones((D,))},
            "head": draw.fan_in((D, V))}


def _rope(x, positions, theta: float):
    d2 = x.shape[-1] // 2
    freqs = 1.0 / theta ** (jnp.arange(0, 2 * d2, 2, dtype=jnp.float32)
                            / (2 * d2))
    ang = positions[:, None].astype(jnp.float32) * freqs      # (S, d2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]   # (S, 1, d2)
    x1, x2 = x[..., :d2], x[..., d2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(p, x, m: dict, prec):
    a, eps = m["attn"], m["norm_eps"]
    B, S, _ = x.shape
    H, KV, hd = a["n_heads"], a["n_kv_heads"], a["head_dim"]
    pos = jnp.arange(S)
    h = rmsnorm(p["norm1"]["scale"], x, eps)
    q = _rope(dot(h, p["attn"]["wq"], prec).reshape(B, S, H, hd), pos,
              a["rope_theta"])
    k = _rope(dot(h, p["attn"]["wk"], prec).reshape(B, S, KV, hd), pos,
              a["rope_theta"])
    v = dot(h, p["attn"]["wv"], prec).reshape(B, S, KV, hd)
    k, v = (jnp.repeat(t, H // KV, axis=2) for t in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", operand(q, prec), operand(k, prec),
                   preferred_element_type=jnp.float32) * hd ** -0.5
    s = jnp.where(pos[:, None] >= pos[None, :], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", operand(w, prec), operand(v, prec))
    o = o.reshape(B, S, H * hd)
    x = x + dot(o, p["attn"]["wo"], prec).astype(x.dtype)
    h = rmsnorm(p["norm2"]["scale"], x, eps)
    mlp = p["mlp"]
    up = jax.nn.silu(dot(h, mlp["gate"], prec)) * dot(h, mlp["up"], prec)
    return x + dot(up, mlp["down"], prec).astype(x.dtype)


def loss(params, tokens, m: dict, precision: str = "float32"):
    """Mean next-token cross entropy of ``tokens`` (B, S)."""
    x = params["embed"][tokens]
    layers = params["periods"]["layers"][0]
    body = jax.checkpoint(lambda x, p: (_layer(p, x, m, precision), None))
    x, _ = jax.lax.scan(body, x, layers)
    h = rmsnorm(params["final_norm"]["scale"], x, m["norm_eps"])
    return next_token_ce(h, params["head"], tokens, precision)
