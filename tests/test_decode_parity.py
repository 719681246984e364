"""Decode (serve_step) vs full-forward teacher-forcing parity.

For every architecture family, step-by-step decoding with the KV/state cache
must reproduce the full-sequence forward logits.  MoE archs use a capacity
factor large enough that no token drops (capacity-based dropping, on the
expert-parallel path only, is the one legitimate train/decode divergence)."""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_smoke_config
from repro.models.model import (_head_weight, decode_step, init_decode_states,
                                init_model, model_forward)
from repro.models.norms import rmsnorm

ARCHS = ["phi3-mini-3.8b", "gemma-2b", "gemma2-2b", "rwkv6-7b",
         "jamba-1.5-large-398b", "deepseek-v3-671b", "phi3.5-moe-42b-a6.6b",
         "musicgen-large", "internvl2-2b", "deepseek-7b", "deepseek-v2-lite-16b"]

B, S = 2, 32


def _full_logits(params, tokens, cfg):
    h, _, _ = model_forward(params, tokens, cfg, remat=False)
    h = rmsnorm(params["final_norm"], h, cfg.norm_eps, cfg.zero_centered_norm)
    if cfg.n_codebooks > 1:
        logits = jnp.stack([h @ _head_weight(params, cfg, cb)
                            for cb in range(cfg.n_codebooks)], axis=2)
    else:
        logits = h @ _head_weight(params, cfg)
    if cfg.logit_softcap:
        logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
    return logits


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_matches_forward(arch):
    key = jax.random.PRNGKey(1)
    cfg = get_smoke_config(arch).replace(prefix_len=0, mtp_depth=0)
    if cfg.moe is not None:
        cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, capacity_factor=16.0))
    params = init_model(key, cfg)
    shape = (B, cfg.n_codebooks, S) if cfg.n_codebooks > 1 else (B, S)
    tokens = jax.random.randint(key, shape, 0, cfg.vocab_size)

    full = _full_logits(params, tokens, cfg)

    states = init_decode_states(B, S, cfg)
    step = jax.jit(lambda p, t, pos, st: decode_step(p, t, pos, st, cfg))
    outs = []
    for t in range(S):
        tok = tokens[:, :, t] if cfg.n_codebooks > 1 else tokens[:, t]
        logits, states = step(params, tok, jnp.int32(t), states)
        outs.append(logits)
    dec = jnp.stack(outs, axis=1)

    err = float(jnp.max(jnp.abs(dec - full.astype(dec.dtype))))
    assert err < 2e-3, f"{arch}: decode/forward mismatch {err}"


def test_sliding_window_ring_cache():
    """Gemma2-style local layer: ring cache must equal a full cache + mask."""
    key = jax.random.PRNGKey(2)
    cfg = get_smoke_config("gemma2-2b").replace(prefix_len=0)
    params = init_model(key, cfg)
    S_long = 96  # > smoke window of 64 so eviction actually happens
    tokens = jax.random.randint(key, (B, S_long), 0, cfg.vocab_size)

    full = _full_logits(params, tokens, cfg)

    states = init_decode_states(B, S_long, cfg)
    step = jax.jit(lambda p, t, pos, st: decode_step(p, t, pos, st, cfg))
    for t in range(S_long):
        logits, states = step(params, tokens[:, t], jnp.int32(t), states)
    err = float(jnp.max(jnp.abs(logits - full[:, -1].astype(logits.dtype))))
    assert err < 2e-3, f"ring-cache mismatch at final position: {err}"
