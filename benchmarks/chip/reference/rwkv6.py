"""Plain RWKV-6 "Finch" block (arXiv:2404.05892) as the program states it.

Per layer, with ``h = n1(x)`` and ``h'`` the previous token's ``h`` (zero
before the first):

* five mixed streams ``h + (h' - h) * clip(mix_base + tanh((h + (h'-h)/2)
  A) B, 0, 1)`` for r, k, v, w, g (the low-rank data-dependent lerp);
* ``r, k, v = x_r Wr, x_k Wk, x_v Wv``; ``g = silu(x_g Wg)``; decay
  ``w = exp(-exp(w0 + tanh(x_w Aw) Bw))``;
* per head (size 64) the recurrence ``o_t = r_t (S + diag(u) k_t^T v_t)``,
  ``S <- diag(w_t) S + k_t^T v_t``, one token at a time in float32;
* a per-head RMSNorm of ``o``, times ``g``, through ``Wout``, added to x;
* channel mix on ``h = n2(x)``: ``sigmoid(x_r Wr) * (relu(x_k Wk)^2 Wv)``
  with fixed token-shift mixes, added to x.

Departures from the published block, which the program makes and the
reference follows: RMSNorm in place of LayerNorm and GroupNorm, no ``ln0``
after the embedding, and a fixed 1/2 in the first lerp.  The program's
chunked form clamps ``w0 + tanh(.) Bw`` to [-20, 0]; the recurrence here
does not, and the weights this benchmark draws keep it inside.  Every
weight matmul takes its operands in ``precision`` (``common.operand``);
the decay, the
recurrence and the norms stay in float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .common import dot, next_token_ce, operand, rmsnorm

_TIME_CHUNK = 64     # tokens per checkpointed block of the recurrence


def init(draw, m: dict):
    L, D, V, F = m["n_layers"], m["d_model"], m["vocab_size"], m["d_ff"]
    r = m["rwkv"]
    R, Rw, hd = r["mix_lora"], r["decay_lora"], r["head_dim"]
    tm = {
        "mix_base": draw.uniform((L, 5, D), 0.0, 0.5),
        "mix_lora_a": draw.fan_in((L, D, 5 * R)),
        "mix_lora_b": draw.uniform((L, 5, R, D), -0.01, 0.01),
        "wr": draw.fan_in((L, D, D)), "wk": draw.fan_in((L, D, D)),
        "wv": draw.fan_in((L, D, D)), "wg": draw.fan_in((L, D, D)),
        "w0": draw.uniform((L, D), -8.0, -4.0),
        "w_lora_a": draw.fan_in((L, D, Rw)),
        "w_lora_b": draw.uniform((L, Rw, D), -0.01, 0.01),
        "u": draw.uniform((L, D), 0.0, 0.5),
        "ln_x": {"scale": draw.ones((L, hd))},
        "out": draw.fan_in((L, D, D)),
    }
    cm = {"mix_k": draw.uniform((L, D), 0.0, 0.5),
          "mix_r": draw.uniform((L, D), 0.0, 0.5),
          "wk": draw.fan_in((L, D, F)), "wr": draw.fan_in((L, D, D)),
          "wv": draw.fan_in((L, F, D))}
    layer = {"norm1": {"scale": draw.ones((L, D))}, "rwkv_tm": tm,
             "norm2": {"scale": draw.ones((L, D))}, "rwkv_cm": cm}
    return {"embed": draw.normal((V, D), 0.02),
            "periods": {"layers": (layer,)},
            "final_norm": {"scale": draw.ones((D,))},
            "head": draw.fan_in((D, V))}


def _shift(h):
    return jnp.concatenate([jnp.zeros_like(h[:, :1]), h[:, :-1]], axis=1)


def _wkv(r, k, v, w, u):
    """r, k, v, w: (B, S, H, d); u: (H, d).  Returns o (B, S, H, d)."""
    B, S, H, d = r.shape

    def token(state, x):
        rt, kt, vt, wt = x                                  # (B, H, d)
        kv = kt[..., :, None] * vt[..., None, :]            # (B, H, d, d)
        o = jnp.einsum("bhk,bhkv->bhv", rt, state + u[..., None] * kv)
        return wt[..., None] * state + kv, o

    def block(state, xs):
        return lax.scan(token, state, xs)

    n = S // _TIME_CHUNK
    xs = tuple(t.transpose(1, 0, 2, 3).reshape(n, _TIME_CHUNK, B, H, d)
               for t in (r, k, v, w))
    state = jnp.zeros((B, H, d, d), jnp.float32)
    _, o = lax.scan(jax.checkpoint(block), state, xs)
    return o.reshape(S, B, H, d).transpose(1, 0, 2, 3)


def _layer(p, x, m: dict, prec):
    eps, hd = m["norm_eps"], m["rwkv"]["head_dim"]
    B, S, D = x.shape
    H = D // hd
    f32 = jnp.float32
    t = p["rwkv_tm"]
    h = rmsnorm(p["norm1"]["scale"], x, eps)
    dh = _shift(h) - h
    lora = jnp.tanh(dot(h + dh * 0.5, t["mix_lora_a"], prec)).reshape(B, S, 5,
                                                                    -1)
    adj = jnp.einsum("bsfr,frd->bsfd", operand(lora, prec),
                     operand(t["mix_lora_b"], prec))
    mix = jnp.clip(t["mix_base"] + adj.astype(f32), 0.0, 1.0)
    xr, xk, xv, xw, xg = (h + dh * mix[:, :, i] for i in range(5))
    r, k, v = dot(xr, t["wr"], prec), dot(xk, t["wk"], prec), dot(xv, t["wv"], prec)
    g = jax.nn.silu(dot(xg, t["wg"], prec))
    lora_w = dot(jnp.tanh(dot(xw, t["w_lora_a"], prec)), t["w_lora_b"], prec)
    w = jnp.exp(-jnp.exp(t["w0"] + lora_w.astype(f32)))
    heads = [a.reshape(B, S, H, hd).astype(f32) for a in (r, k, v, w)]
    o = _wkv(*heads, t["u"].reshape(H, hd))
    o = rmsnorm(t["ln_x"]["scale"], o, 1e-6).reshape(B, S, D)
    x = x + dot(o * g, t["out"], prec).astype(x.dtype)

    c = p["rwkv_cm"]
    h = rmsnorm(p["norm2"]["scale"], x, eps)
    dh = _shift(h) - h
    xk, xr = h + dh * c["mix_k"], h + dh * c["mix_r"]
    kk = jnp.square(jax.nn.relu(dot(xk, c["wk"], prec)))
    cm = jax.nn.sigmoid(dot(xr, c["wr"], prec)) * dot(kk, c["wv"], prec)
    return x + cm.astype(x.dtype)


def loss(params, tokens, m: dict, precision: str = "float32"):
    """Mean next-token cross entropy of ``tokens`` (B, S)."""
    x = params["embed"][tokens]
    layers = params["periods"]["layers"][0]
    body = jax.checkpoint(lambda x, p: (_layer(p, x, m, precision), None))
    x, _ = lax.scan(body, x, layers)
    h = rmsnorm(params["final_norm"]["scale"], x, m["norm_eps"])
    return next_token_ce(h, params["head"], tokens, precision)
