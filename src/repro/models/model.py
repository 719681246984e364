"""CausalLM assembly: embedding -> dense prefix -> scanned periods -> norm
-> head (+MTP).

Covers every assigned architecture:

* text LMs (dense / MoE / SSM / RWKV / hybrid),
* MusicGen-style multi-codebook audio decoding (sum of codebook embeddings,
  one head per codebook; the EnCodec frontend is a stub — see
  ``frontend.py``),
* VLM (InternVL2): stub vision embeddings are projected and prepended as a
  prefix; loss is masked to text positions,
* DeepSeek-V3 MTP: one extra transformer block predicting token t+2 from
  [emb(t+1); h_t], sharing embedding and head.

Sharding note: this module is written for single-device semantics; the
distributed runtime reuses ``apply_periods`` inside shard_map and adds
sharding constraints around the embed/head (auto mode).  The loss is
computed in sequence chunks so (B, S, V) logits are never materialized.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .blocks import (apply_dense_prefix, apply_period, apply_periods,
                     decode_dense_prefix, decode_periods, init_dense_prefix,
                     init_dense_prefix_states, init_period, init_period_states,
                     init_periods)
from .config import ModelConfig
from .module import NO_PARALLEL, ParallelCtx, dense_init, embed_init, split_keys, vscan
from .norms import init_rmsnorm, rmsnorm

MTP_WEIGHT = 0.3


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def init_model(key, cfg: ModelConfig):
    ks = split_keys(key, 6)
    d, v, dtype = cfg.d_model, cfg.vocab_size, cfg.pdtype
    params = {
        "embed": embed_init(ks[0], (cfg.n_codebooks, v, d) if cfg.n_codebooks > 1 else (v, d), dtype),
        "periods": init_periods(ks[1], cfg),
        "final_norm": init_rmsnorm(ks[2], d, dtype, cfg.zero_centered_norm),
    }
    if cfg.first_k_dense:
        params["dense_prefix"] = init_dense_prefix(jax.random.fold_in(key, 15), cfg)
    if not cfg.tie_embeddings:
        params["head"] = dense_init(
            ks[3], (cfg.n_codebooks, d, v) if cfg.n_codebooks > 1 else (d, v),
            in_dim=d, dtype=dtype)
    if cfg.prefix_len > 0:
        # frontend stub projector (frontend_dim -> d_model); frontend_dim
        # rides in as half of d_model by convention of frontend.py
        from .frontend import frontend_dim
        params["prefix_proj"] = dense_init(ks[4], (frontend_dim(cfg), d),
                                           in_dim=frontend_dim(cfg), dtype=dtype)
    if cfg.mtp_depth > 0:
        params["mtp"] = {
            "combine": dense_init(ks[5], (2 * d, d), in_dim=2 * d, dtype=dtype),
            "norm_h": init_rmsnorm(jax.random.fold_in(key, 11), d, dtype, cfg.zero_centered_norm),
            "norm_e": init_rmsnorm(jax.random.fold_in(key, 12), d, dtype, cfg.zero_centered_norm),
            "block": init_period(jax.random.fold_in(key, 13), cfg),
            "final_norm": init_rmsnorm(jax.random.fold_in(key, 14), d, dtype, cfg.zero_centered_norm),
        }
    return params


# ---------------------------------------------------------------------------
# Embedding / head helpers
# ---------------------------------------------------------------------------


def embed_tokens(params, tokens, cfg: ModelConfig):
    """tokens: (B, S) or (B, n_codebooks, S) -> (B, S, D)."""
    if cfg.n_codebooks > 1:
        x = sum(params["embed"][cb][tokens[:, cb]] for cb in range(cfg.n_codebooks))
    else:
        x = params["embed"][tokens]
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    return x.astype(cfg.cdtype)


def _head_weight(params, cfg: ModelConfig, codebook: int | None = None):
    if cfg.tie_embeddings:
        w = params["embed"]
        w = (w[codebook] if cfg.n_codebooks > 1 else w).T
    else:
        w = params["head"]
        w = w[codebook] if cfg.n_codebooks > 1 else w
    return w


def chunked_ce_loss(h, head_w, targets, mask, softcap=None, chunk: int = 2048):
    """Cross entropy without materializing full logits.

    h: (B, S, D); head_w: (D, V); targets/mask: (B, S).  Returns (sum_loss,
    sum_count, sum_correct).
    """
    B, S, D = h.shape
    chunk = min(chunk, S)
    n = -(-S // chunk)
    pad = n * chunk - S
    if pad:
        h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    hc = h.reshape(B, n, chunk, D).transpose(1, 0, 2, 3)
    tc = targets.reshape(B, n, chunk).transpose(1, 0, 2)
    mc = mask.reshape(B, n, chunk).transpose(1, 0, 2)

    def step(carry, args):
        s_loss, s_cnt, s_acc = carry
        hi, ti, mi = args
        logits = (hi @ head_w).astype(jnp.float32)
        if softcap is not None:
            logits = softcap * jnp.tanh(logits / softcap)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, ti[..., None], axis=-1)[..., 0]
        loss = (lse - gold) * mi
        acc = (logits.argmax(-1) == ti) * mi
        return (s_loss + loss.sum(), s_cnt + mi.sum(), s_acc + acc.sum()), None

    zero = (jnp.zeros((), jnp.float32),) * 3
    (s_loss, s_cnt, s_acc), _ = vscan(step, zero, (hc, tc, mc))
    return s_loss, s_cnt, s_acc


# ---------------------------------------------------------------------------
# Forward / loss
# ---------------------------------------------------------------------------


def model_forward(params, tokens, cfg: ModelConfig, ctx: ParallelCtx = NO_PARALLEL,
                  prefix: jnp.ndarray | None = None, remat: bool = True):
    """Backbone forward.  tokens (B,S) or (B,CB,S); prefix (B,P,F) stub embeds.

    Returns (h (B, S_total, D), stats, positions).
    """
    x = embed_tokens(params, tokens, cfg)
    B = x.shape[0]
    if cfg.prefix_len > 0:
        assert prefix is not None, "frontend prefix embeddings required"
        px = (prefix.astype(cfg.cdtype) @ params["prefix_proj"]).astype(cfg.cdtype)
        x = jnp.concatenate([px, x], axis=1)
    S_total = x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(S_total, dtype=jnp.int32), (B, S_total))
    if cfg.first_k_dense:
        x = apply_dense_prefix(params["dense_prefix"], x, positions, cfg, ctx, remat)
    h, stats = apply_periods(params["periods"], x, positions, cfg, ctx, remat=remat)
    return h, stats, positions


def loss_fn(params, batch, cfg: ModelConfig, ctx: ParallelCtx = NO_PARALLEL,
            remat: bool = True, ce_chunk: int = 2048):
    """Next-token LM loss.  batch: {"tokens", optional "prefix", optional "mask"}.

    Returns (loss, metrics dict).
    """
    tokens = batch["tokens"]
    h, stats, _ = model_forward(params, tokens, cfg, ctx, batch.get("prefix"), remat)
    aux = stats["aux"]
    h = rmsnorm(params["final_norm"], h, cfg.norm_eps, cfg.zero_centered_norm)
    if cfg.prefix_len > 0:
        h = h[:, cfg.prefix_len:]         # loss on text positions only

    if cfg.n_codebooks > 1:
        total, count, correct = 0.0, 0.0, 0.0
        for cb in range(cfg.n_codebooks):
            t_in = tokens[:, cb]
            tgt = t_in[:, 1:]
            mask = batch.get("mask", jnp.ones_like(t_in))[:, 1:].astype(jnp.float32)
            l, c, a = chunked_ce_loss(h[:, :-1], _head_weight(params, cfg, cb),
                                      tgt, mask, cfg.logit_softcap, ce_chunk)
            total, count, correct = total + l, count + c, correct + a
    else:
        tgt = tokens[:, 1:]
        mask = batch.get("mask", jnp.ones_like(tokens))[:, 1:].astype(jnp.float32)
        total, count, correct = chunked_ce_loss(
            h[:, :-1], _head_weight(params, cfg), tgt, mask,
            cfg.logit_softcap, ce_chunk)

    loss = total / jnp.maximum(count, 1.0)
    metrics = {"ce": loss, "aux": aux, "acc": correct / jnp.maximum(count, 1.0),
               "tokens": count}
    if cfg.moe is not None:
        metrics["expert_rows"] = stats["expert_rows"]
    loss = loss + aux

    if cfg.mtp_depth > 0 and cfg.n_codebooks == 1:
        mtp_loss = _mtp_loss(params, h if cfg.prefix_len == 0 else h, tokens, cfg, ctx,
                             batch.get("mask"), ce_chunk)
        metrics["mtp"] = mtp_loss
        loss = loss + MTP_WEIGHT * mtp_loss
    return loss, metrics


def _mtp_loss(params, h, tokens, cfg: ModelConfig, ctx, mask, ce_chunk):
    """DeepSeek-V3 multi-token prediction (depth 1): predict t+2 from
    [norm(emb(t+1)); norm(h_t)] -> combine -> 1 block -> shared head."""
    m = params["mtp"]
    B, S = tokens.shape
    emb_next = embed_tokens(params, tokens, cfg)       # (B,S,D) — emb(token_t)
    # position t uses h_t and emb(t+1): shift emb left by 1
    e = jnp.concatenate([emb_next[:, 1:], jnp.zeros_like(emb_next[:, :1])], axis=1)
    zc = cfg.zero_centered_norm
    hh = jnp.concatenate([rmsnorm(m["norm_e"], e, cfg.norm_eps, zc),
                          rmsnorm(m["norm_h"], h, cfg.norm_eps, zc)], axis=-1)
    hh = (hh @ m["combine"]).astype(cfg.cdtype)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    hh, _ = apply_period(m["block"], hh, positions, cfg, ctx)
    hh = rmsnorm(m["final_norm"], hh, cfg.norm_eps, zc)
    # target at position t is token t+2
    tgt = jnp.concatenate([tokens[:, 2:], jnp.zeros_like(tokens[:, :2])], axis=1)
    msk = jnp.ones_like(tokens, jnp.float32) if mask is None else mask.astype(jnp.float32)
    valid = jnp.arange(S) < S - 2
    msk = msk * valid[None, :]
    l, c, _ = chunked_ce_loss(hh, _head_weight(params, cfg), tgt, msk,
                              cfg.logit_softcap, ce_chunk)
    return l / jnp.maximum(c, 1.0)


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------


def init_decode_states(batch: int, max_len: int, cfg: ModelConfig,
                       seq_shards: int = 1):
    """Period states; with a dense prefix ``{"dense_prefix", "periods"}``.
    Every leaf is stacked over layers, batch on axis 1."""
    states = init_period_states(batch, max_len, cfg, cfg.cdtype, seq_shards)
    if not cfg.first_k_dense:
        return states
    return {"dense_prefix": init_dense_prefix_states(batch, max_len, cfg, cfg.cdtype,
                                                     seq_shards),
            "periods": states}


def decode_step(params, token, position, states, cfg: ModelConfig,
                ctx: ParallelCtx = NO_PARALLEL):
    """One decode step.

    token: (B,) int32 (or (B, CB) for multi-codebook); position: () int32,
    or (B,) int32 to decode each row at its own position (continuous
    batching — attention masks per-row; recurrent archs are position-free).
    Returns (logits (B, V) or (B, CB, V), new_states).
    """
    if cfg.n_codebooks > 1:
        x = sum(params["embed"][cb][token[:, cb]] for cb in range(cfg.n_codebooks))
    else:
        x = params["embed"][token]
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    x = x.astype(cfg.cdtype)

    if cfg.first_k_dense:
        x, prefix_states = decode_dense_prefix(params["dense_prefix"], x, position,
                                               states["dense_prefix"], cfg, ctx)
        states = states["periods"]
    h, new_states = decode_periods(params["periods"], x, position, states, cfg, ctx)
    if cfg.first_k_dense:
        new_states = {"dense_prefix": prefix_states, "periods": new_states}
    h = rmsnorm(params["final_norm"], h, cfg.norm_eps, cfg.zero_centered_norm)

    if cfg.n_codebooks > 1:
        logits = jnp.stack([
            (h @ _head_weight(params, cfg, cb)).astype(jnp.float32)
            for cb in range(cfg.n_codebooks)], axis=1)
    else:
        logits = (h @ _head_weight(params, cfg)).astype(jnp.float32)
    if cfg.logit_softcap is not None:
        logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
    return logits, new_states
