"""Block assembly: the repeating layer pattern, scanned over periods.

A model is ``n_periods`` repetitions of ``cfg.pattern`` (a tuple of
``LayerSpec``).  Period parameters are stacked along a leading axis so the
decoder body is a single ``lax.scan`` — this keeps the HLO size independent
of depth and gives the pipeline runtime a natural unit to slice into stages
(stage = consecutive periods).

All apply functions take the *localized* config (``ModelConfig.shard``) so
the same code runs single-device and under shard_map tensor/expert
parallelism.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from repro import scopes

from .attention import (AttentionConfig, attention_decode, attention_forward,
                        init_attention, init_attention_cache)
from .config import LayerSpec, ModelConfig
from .mlp import init_mlp, mlp
from .module import ParallelCtx, NO_PARALLEL, split_keys, vmap_init, vscan
from .moe import init_moe, moe
from .norms import init_rmsnorm, rmsnorm
from .rwkv import (init_rwkv_channel_mix, init_rwkv_state, init_rwkv_time_mix,
                   rwkv_channel_mix, rwkv_channel_mix_decode, rwkv_time_mix,
                   rwkv_time_mix_decode)
from .ssm import init_mamba, init_mamba_state, mamba_decode, mamba_forward


def shard_config(cfg: ModelConfig, tp: int = 1, ep: int = 1) -> ModelConfig:
    """Localize a global config for one (tp, ep) shard."""
    if tp == 1 and ep == 1:
        return cfg
    new = {}
    if cfg.attn is not None:
        new["attn"] = cfg.attn.local(tp)
    if cfg.moe is not None:
        new["moe"] = cfg.moe.local(ep, tp)
    new["d_ff"] = cfg.d_ff // tp
    return cfg.replace(**new)


def _attn_cfg(cfg: ModelConfig, spec: LayerSpec) -> AttentionConfig:
    a = cfg.attn
    if not spec.full_attention or spec.window is not None:
        a = dataclasses.replace(a, window=spec.window)
    return a


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def init_layer(key, cfg: ModelConfig, spec: LayerSpec):
    """One layer's params — GLOBAL shapes (sharding happens via pjit specs)."""
    ks = split_keys(key, 6)
    d, dtype = cfg.d_model, cfg.pdtype
    p = {"norm1": init_rmsnorm(ks[0], d, dtype, cfg.zero_centered_norm)}
    if spec.kind == "attn":
        p["attn"] = init_attention(ks[1], d, cfg.attn, dtype)
    elif spec.kind == "mamba":
        p["mamba"] = init_mamba(ks[1], d, cfg.mamba, tp=1, dtype=dtype)
    elif spec.kind == "rwkv":
        p["rwkv_tm"] = init_rwkv_time_mix(ks[1], d, cfg.rwkv, tp=1, dtype=dtype)
    else:
        raise ValueError(spec.kind)

    if spec.mlp != "none":
        p["norm2"] = init_rmsnorm(ks[2], d, dtype, cfg.zero_centered_norm)
    if spec.mlp == "mlp":
        gated = cfg.act in ("silu", "gelu_tanh", "gelu")
        p["mlp"] = init_mlp(ks[3], d, cfg.d_ff, act=cfg.act, gated=gated, dtype=dtype)
    elif spec.mlp == "moe":
        p["moe"] = init_moe(ks[3], d, cfg.moe, dtype=dtype)
    elif spec.mlp == "rwkv_cm":
        p["rwkv_cm"] = init_rwkv_channel_mix(ks[3], d, cfg.d_ff, dtype)

    if cfg.post_norms:
        p["norm1_post"] = init_rmsnorm(ks[4], d, dtype, cfg.zero_centered_norm)
        if spec.mlp != "none":
            p["norm2_post"] = init_rmsnorm(ks[5], d, dtype, cfg.zero_centered_norm)
    return p


def init_period(key, cfg: ModelConfig):
    ks = split_keys(key, len(cfg.pattern))
    return {"layers": tuple(init_layer(k, cfg, s) for k, s in zip(ks, cfg.pattern))}


def init_periods(key, cfg: ModelConfig):
    """Stacked params for all periods: leaves have leading dim n_periods."""
    return vmap_init(init_period, key, cfg.n_periods, cfg)


# ---------------------------------------------------------------------------
# Forward (training / prefill)
# ---------------------------------------------------------------------------


def apply_layer(params, x, positions, cfg: ModelConfig, spec: LayerSpec,
                ctx: ParallelCtx = NO_PARALLEL):
    """Returns (x, aux_loss)."""
    eps, zc = cfg.norm_eps, cfg.zero_centered_norm
    aux = jnp.zeros((), jnp.float32)
    h = rmsnorm(params["norm1"], x, eps, zc)
    if spec.kind == "attn":
        with scopes.scope(scopes.ATTENTION):
            h = attention_forward(params["attn"], h, positions,
                                  _attn_cfg(cfg, spec), ctx)
    elif spec.kind == "mamba":
        h, _ = mamba_forward(params["mamba"], h, cfg.mamba, ctx)
    elif spec.kind == "rwkv":
        h, _ = rwkv_time_mix(params["rwkv_tm"], h, cfg.rwkv, ctx)
    if cfg.post_norms:
        h = rmsnorm(params["norm1_post"], h, eps, zc)
    x = x + h.astype(x.dtype)

    if spec.mlp == "none":
        return x, aux
    h = rmsnorm(params["norm2"], x, eps, zc)
    if spec.mlp == "mlp":
        with scopes.scope(scopes.MLP):
            h = mlp(params["mlp"], h, act=cfg.act, ctx=ctx)
    elif spec.mlp == "moe":
        h, aux = moe(params["moe"], h, cfg.moe, cfg.moe.n_experts_global or cfg.moe.n_experts, ctx)
    elif spec.mlp == "rwkv_cm":
        h, _ = rwkv_channel_mix(params["rwkv_cm"], h, ctx)
    if cfg.post_norms:
        h = rmsnorm(params["norm2_post"], h, eps, zc)
    return x + h.astype(x.dtype), aux


def apply_period(params, x, positions, cfg: ModelConfig, ctx: ParallelCtx = NO_PARALLEL):
    aux = jnp.zeros((), jnp.float32)
    for p, spec in zip(params["layers"], cfg.pattern):
        x, a = apply_layer(p, x, positions, cfg, spec, ctx)
        aux = aux + a
    return x, aux


def apply_periods(stacked, x, positions, cfg: ModelConfig,
                  ctx: ParallelCtx = NO_PARALLEL, remat: bool = True):
    """Scan the stacked periods.  Returns (x, total_aux)."""

    def body(carry, period_params):
        h, aux = carry
        h, a = apply_period(period_params, h, positions, cfg, ctx)
        return (h, aux + a), None

    fn = jax.checkpoint(body) if remat else body
    (x, aux), _ = vscan(fn, (x, jnp.zeros((), jnp.float32)), stacked)
    return x, aux


# ---------------------------------------------------------------------------
# Decode (one token)
# ---------------------------------------------------------------------------


def decode_layer(params, x, position, state, cfg: ModelConfig, spec: LayerSpec,
                 ctx: ParallelCtx = NO_PARALLEL):
    """x: (B, D) one position.  Returns (x, new_state)."""
    eps, zc = cfg.norm_eps, cfg.zero_centered_norm
    h = rmsnorm(params["norm1"], x, eps, zc)
    if spec.kind == "attn":
        h, state_m = attention_decode(params["attn"], h, position, state["mixer"],
                                      _attn_cfg(cfg, spec), ctx)
    elif spec.kind == "mamba":
        h, state_m = mamba_decode(params["mamba"], h, cfg.mamba, state["mixer"], ctx)
    elif spec.kind == "rwkv":
        h, state_m = rwkv_time_mix_decode(params["rwkv_tm"], h, cfg.rwkv, state["mixer"], ctx)
    if cfg.post_norms:
        h = rmsnorm(params["norm1_post"], h, eps, zc)
    x = x + h.astype(x.dtype)

    state_c = state.get("cm")
    if spec.mlp != "none":
        h = rmsnorm(params["norm2"], x, eps, zc)
        if spec.mlp == "mlp":
            h = mlp(params["mlp"], h, act=cfg.act, ctx=ctx)
        elif spec.mlp == "moe":
            h, _ = moe(params["moe"], h, cfg.moe,
                       cfg.moe.n_experts_global or cfg.moe.n_experts, ctx)
        elif spec.mlp == "rwkv_cm":
            h, state_c = rwkv_channel_mix_decode(params["rwkv_cm"], h, state["cm"], ctx)
        if cfg.post_norms:
            h = rmsnorm(params["norm2_post"], h, eps, zc)
        x = x + h.astype(x.dtype)
    new_state = {"mixer": state_m}
    if state_c is not None:
        new_state["cm"] = state_c
    return x, new_state


def decode_period(params, x, position, states, cfg: ModelConfig,
                  ctx: ParallelCtx = NO_PARALLEL):
    new_states = []
    for p, spec, st in zip(params["layers"], cfg.pattern, states):
        x, ns = decode_layer(p, x, position, st, cfg, spec, ctx)
        new_states.append(ns)
    return x, tuple(new_states)


def decode_periods(stacked, x, position, states, cfg: ModelConfig,
                   ctx: ParallelCtx = NO_PARALLEL):
    """Scan decode over stacked periods; states stacked the same way."""

    def body(h, inputs):
        period_params, st = inputs
        h, ns = decode_period(period_params, h, position, st, cfg, ctx)
        return h, ns

    x, new_states = vscan(body, x, (stacked, states))
    return x, new_states


# ---------------------------------------------------------------------------
# State init
# ---------------------------------------------------------------------------


def init_layer_state(batch: int, max_len: int, cfg: ModelConfig, spec: LayerSpec,
                     dtype, seq_shards: int = 1):
    if spec.kind == "attn":
        # Sliding-window layers only need `window` cache slots.
        a = _attn_cfg(cfg, spec)
        eff_len = max_len if a.window is None else min(max_len, a.window)
        eff_len = max(eff_len, seq_shards)
        eff_len = -(-eff_len // seq_shards) * seq_shards
        st = {"mixer": init_attention_cache(batch, eff_len, a, dtype, seq_shards)}
    elif spec.kind == "mamba":
        st = {"mixer": init_mamba_state(batch, cfg.d_model, cfg.mamba, tp=1, dtype=dtype)}
    elif spec.kind == "rwkv":
        full = init_rwkv_state(batch, cfg.d_model, cfg.rwkv, tp=1, dtype=dtype)
        st = {"mixer": full["tm"]}
        if spec.mlp == "rwkv_cm":
            st["cm"] = full["cm"]
        return st
    else:
        raise ValueError(spec.kind)
    return st


def init_period_states(batch: int, max_len: int, cfg: ModelConfig, dtype,
                       seq_shards: int = 1):
    """Stacked decode states: leaves get leading dim n_periods.

    NOTE: uses the *localized* cfg — shapes here are per-shard.
    """
    one = tuple(init_layer_state(batch, max_len, cfg, s, dtype, seq_shards)
                for s in cfg.pattern)
    return jax.tree.map(lambda x: jnp.broadcast_to(x, (cfg.n_periods, *x.shape)).copy(), one)
