"""Attention: GQA/MQA, sliding-window, logit softcap, MLA — TP-aware.

Two execution paths:

* ``blocked_causal_attention`` — training / prefill.  Exact causal (and
  optionally sliding-window) attention computed in (q-chunk × kv-chunk)
  blocks with an online-softmax accumulator, so the full S×S score matrix is
  never materialized.  The q-chunk loop is a *static* Python loop whose
  kv-range is trimmed per chunk — no wasted FLOPs on fully-masked blocks
  (this is the XLA-native analogue of the Pallas flash kernel in
  ``repro.kernels.flash_attention``).

* ``decode_attention`` — serve_step: one query token against a KV cache.
  Supports a sequence-sharded cache (flash-decoding style): each shard
  computes a partial softmax over its KV slice and the results are combined
  with ``pmax``/``psum`` over ``ctx.seq_axis``.

TP layout (Megatron): q/k/v projections column-parallel over heads, output
projection row-parallel (+psum).  Layer code sees local head counts.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from repro import scopes

from .module import ParallelCtx, NO_PARALLEL, dense_init, split_keys, vscan
from .norms import init_rmsnorm, rmsnorm
from .rotary import YaRN, rope_cos_sin, apply_rope, apply_rope_partial

NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    """DeepSeek Multi-head Latent Attention dims (per head unless noted).

    ``q_lora_rank`` None: the query is one projection ``x @ wq`` with no
    low-rank bottleneck (DeepSeek-V2-Lite)."""

    q_lora_rank: int | None = 1536
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128


@dataclasses.dataclass(frozen=True)
class AttentionConfig:
    n_heads: int
    n_kv_heads: int
    head_dim: int
    rope_theta: float = 10000.0
    window: int | None = None          # sliding-window size (None = full)
    softcap: float | None = None       # attn logit softcapping (Gemma2)
    mla: MLAConfig | None = None
    rope_scaling: YaRN | None = None   # YaRN context extension (DeepSeek-V2)
    q_chunk: int = 1024
    kv_chunk: int = 1024
    # When tp > n_kv_heads the KV projections are *replicated* across tp
    # shards and each shard dynamically slices its single KV head:
    #   kv_head = tp_index // kv_slice_div .
    kv_slice_div: int | None = None

    def local(self, tp: int) -> "AttentionConfig":
        """Per-shard head counts under tp-way tensor parallelism.

        Query heads are always sharded; KV heads are sharded when divisible
        by ``tp``, otherwise the KV projection weights stay replicated and
        each shard slices out the one KV head its query heads attend to.
        """
        if tp == 1:
            return self
        assert self.n_heads % tp == 0, (self.n_heads, tp)
        if self.n_kv_heads % tp == 0:
            return dataclasses.replace(
                self, n_heads=self.n_heads // tp, n_kv_heads=self.n_kv_heads // tp)
        assert tp % self.n_kv_heads == 0, (self.n_kv_heads, tp)
        return dataclasses.replace(
            self, n_heads=self.n_heads // tp, kv_slice_div=tp // self.n_kv_heads)

    @property
    def cache_kv_heads(self) -> int:
        """KV heads held in the decode cache.

        When KV is replicated across tp (kv_slice_div set) the cache keeps
        *all* KV heads — identical on every tp shard, so the global cache
        array is expressible with a replicated head dim — and the shard's
        head is sliced at attention-compute time."""
        return self.n_kv_heads

    def rope(self, positions, dim: int):
        """cos/sin tables of this config's rope at ``positions``."""
        return rope_cos_sin(positions, dim, self.rope_theta, self.rope_scaling)

    @property
    def remat_query_chunks(self) -> bool:
        """Latent attention runs at DeepSeek's long sequences, so its
        backward keeps one query chunk's score blocks at a time."""
        return self.mla is not None

    def softmax_scale(self, qk_dim: int) -> float:
        scale = qk_dim ** -0.5
        if self.rope_scaling is not None:
            scale *= self.rope_scaling.softmax_scale
        return scale


# ---------------------------------------------------------------------------
# Parameter init
# ---------------------------------------------------------------------------


def init_attention(key, d_model: int, cfg: AttentionConfig, dtype=jnp.float32):
    """Standard GQA attention params with *local* (per-tp-shard) head counts."""
    if cfg.mla is not None:
        return init_mla_attention(key, d_model, cfg, dtype)
    ks = split_keys(key, 4)
    h, kvh, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    return {
        "wq": dense_init(ks[0], (d_model, h * d), in_dim=d_model, dtype=dtype),
        "wk": dense_init(ks[1], (d_model, kvh * d), in_dim=d_model, dtype=dtype),
        "wv": dense_init(ks[2], (d_model, kvh * d), in_dim=d_model, dtype=dtype),
        "wo": dense_init(ks[3], (h * d, d_model), in_dim=h * d, dtype=dtype),
    }


def init_mla_attention(key, d_model: int, cfg: AttentionConfig, dtype=jnp.float32):
    m = cfg.mla
    ks = split_keys(key, 8)
    h = cfg.n_heads
    qk_dim = m.qk_nope_dim + m.qk_rope_dim
    if m.q_lora_rank is None:
        # query path: one projection to per-head (nope+rope)
        q = {"wq": dense_init(ks[0], (d_model, h * qk_dim), in_dim=d_model, dtype=dtype)}
    else:
        # query path: down-proj -> norm -> up-proj to per-head (nope+rope)
        q = {
            "wq_a": dense_init(ks[0], (d_model, m.q_lora_rank), in_dim=d_model, dtype=dtype),
            "q_norm": init_rmsnorm(ks[1], m.q_lora_rank, dtype),
            "wq_b": dense_init(ks[2], (m.q_lora_rank, h * qk_dim), in_dim=m.q_lora_rank, dtype=dtype),
        }
    return {
        **q,
        # kv path: joint down-proj to latent (+ shared rope key)
        "wkv_a": dense_init(ks[3], (d_model, m.kv_lora_rank + m.qk_rope_dim), in_dim=d_model, dtype=dtype),
        "kv_norm": init_rmsnorm(ks[4], m.kv_lora_rank, dtype),
        "wk_b": dense_init(ks[5], (m.kv_lora_rank, h * m.qk_nope_dim), in_dim=m.kv_lora_rank, dtype=dtype),
        "wv_b": dense_init(ks[6], (m.kv_lora_rank, h * m.v_head_dim), in_dim=m.kv_lora_rank, dtype=dtype),
        "wo": dense_init(ks[7], (h * m.v_head_dim, d_model), in_dim=h * m.v_head_dim, dtype=dtype),
    }


# ---------------------------------------------------------------------------
# Blocked exact attention (training / prefill)
# ---------------------------------------------------------------------------


def _chunk_scores(q, k, scale, softcap):
    # q: (B, Cq, Hkv, G, D)  k: (B, Ck, Hkv, D) -> (B, Hkv, G, Cq, Ck)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q.astype(jnp.float32), k.astype(jnp.float32))
    s = s * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    return s


def blocked_causal_attention(
    q: jnp.ndarray,           # (B, S, Hq, D)
    k: jnp.ndarray,           # (B, S, Hkv, D)
    v: jnp.ndarray,           # (B, S, Hkv, Dv)
    *,
    scale: float,
    window: int | None = None,
    softcap: float | None = None,
    q_chunk: int = 1024,
    kv_chunk: int = 1024,
    remat_chunks: bool = False,
) -> jnp.ndarray:
    """Exact causal attention, O(q_chunk × kv_chunk) live score memory.

    ``remat_chunks``: each query chunk is rematerialised in the backward
    pass, so the backward holds one chunk's score blocks at a time instead of
    every chunk's (O(q_chunk × S) rather than O(S² / 2) per layer), at the
    cost of one more forward of the scores.
    """
    B, S, Hq, D = q.shape
    Hkv = k.shape[2]
    Dv = v.shape[-1]
    G = Hq // Hkv
    q_chunk = min(q_chunk, S)
    kv_chunk = min(kv_chunk, S)
    n_q = -(-S // q_chunk)

    # Pad K/V so every dynamic_slice is in bounds (padded tail positions have
    # kv_pos >= S and are always causally masked).
    S_pad = -(-S // kv_chunk) * kv_chunk
    if S_pad != S:
        k = jnp.pad(k, ((0, 0), (0, S_pad - S), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, S_pad - S), (0, 0), (0, 0)))

    qg = q.reshape(B, S, Hkv, G, D)
    out = jnp.zeros((B, S, Hkv, G, Dv), dtype=q.dtype)

    def one_chunk(i, qg, k, v):
        q_lo = i * q_chunk
        q_hi = min(S, q_lo + q_chunk)
        Cq = q_hi - q_lo
        qi = qg[:, q_lo:q_hi]
        # kv range needed by this q chunk (static bounds)
        kv_hi = q_hi
        kv_lo = 0 if window is None else max(0, q_lo - window)
        kv_lo = (kv_lo // kv_chunk) * kv_chunk
        n_kv = -(-(kv_hi - kv_lo) // kv_chunk)

        q_pos = (q_lo + jnp.arange(Cq))[:, None]  # (Cq, 1)

        def kv_step(carry, j):
            m, l, acc = carry
            start = kv_lo + j * kv_chunk
            kj = lax.dynamic_slice_in_dim(k, start, kv_chunk, axis=1)
            vj = lax.dynamic_slice_in_dim(v, start, kv_chunk, axis=1)
            s = _chunk_scores(qi, kj, scale, softcap)  # (B,Hkv,G,Cq,Ck)
            kv_pos = start + jnp.arange(kv_chunk)[None, :]
            mask = kv_pos <= q_pos
            if window is not None:
                mask &= kv_pos > q_pos - window
            # positions beyond S (when kv_chunk doesn't divide) are masked by causality
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            pv = jnp.einsum("bhgqk,bkhd->bhgqd", p, vj.astype(jnp.float32))
            acc_new = acc * corr[..., None] + pv
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, Hkv, G, Cq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Hkv, G, Cq), jnp.float32)
        a0 = jnp.zeros((B, Hkv, G, Cq, Dv), jnp.float32)
        (m, l, acc), _ = vscan(kv_step, (m0, l0, a0), jnp.arange(n_kv))
        oi = (acc / jnp.maximum(l, 1e-37)[..., None]).transpose(0, 3, 1, 2, 4)
        return oi.astype(q.dtype)

    for i in range(n_q):
        chunk = partial(one_chunk, i)
        oi = (jax.checkpoint(chunk) if remat_chunks else chunk)(qg, k, v)
        out = lax.dynamic_update_slice_in_dim(out, oi, i * q_chunk, axis=1)

    return out.reshape(B, S, Hq, Dv)


# ---------------------------------------------------------------------------
# Decode attention (single new token vs KV cache)
# ---------------------------------------------------------------------------


def decode_attention(
    q: jnp.ndarray,            # (B, Hq, D) single position
    k_cache: jnp.ndarray,      # (B, S_local, Hkv, D)   (seq-sharded if ctx.seq_axis)
    v_cache: jnp.ndarray,      # (B, S_local, Hkv, Dv)
    cache_len: jnp.ndarray,    # () or (B,) int32 — valid *global* positions
    *,
    scale: float,
    window: int | None = None,
    softcap: float | None = None,
    ctx: ParallelCtx = NO_PARALLEL,
) -> jnp.ndarray:
    """One-token attention with partial-softmax combine over a sharded cache.

    ``cache_len`` may be per-row ``(B,)`` — continuous batching decodes each
    slot at its own sequence position — or a scalar shared by the batch.
    """
    B, S_local, Hkv, D = k_cache.shape
    Hq = q.shape[1]
    G = Hq // Hkv
    Dv = v_cache.shape[-1]
    qg = q.reshape(B, Hkv, G, D)

    # Global positions owned by this shard.
    shard = ctx.seq_index()
    pos = shard * S_local + jnp.arange(S_local)  # (S_local,)
    if jnp.ndim(cache_len) == 1:
        valid = pos[None, :] < cache_len[:, None]          # (B, S_local)
        if window is not None:
            valid &= pos[None, :] >= cache_len[:, None] - window
        mask = valid[:, None, None, :]
    else:
        valid = pos < cache_len
        if window is not None:
            valid &= pos >= cache_len - window
        mask = valid[None, None, None]

    s = jnp.einsum("bhgd,bkhd->bhgk", qg.astype(jnp.float32), k_cache.astype(jnp.float32)) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    s = jnp.where(mask, s, NEG_INF)

    m_local = s.max(axis=-1)                      # (B,Hkv,G)
    m = ctx.pmax_seq(m_local)
    p = jnp.exp(s - m[..., None])
    l = ctx.psum_seq(p.sum(axis=-1))
    pv = jnp.einsum("bhgk,bkhd->bhgd", p, v_cache.astype(jnp.float32))
    pv = ctx.psum_seq(pv)
    out = pv / jnp.maximum(l, 1e-37)[..., None]
    return out.reshape(B, Hq, Dv).astype(q.dtype)


# ---------------------------------------------------------------------------
# Full attention layers (projections + rope + attention + out-proj)
# ---------------------------------------------------------------------------


def _slice_kv(t: jnp.ndarray, cfg: AttentionConfig, ctx: ParallelCtx) -> jnp.ndarray:
    """Select this shard's KV head when KV projections are replicated."""
    if cfg.kv_slice_div is None:
        return t
    head = ctx.tp_index() // cfg.kv_slice_div
    return lax.dynamic_slice_in_dim(t, head, 1, axis=-2)


def attention_forward(
    params,
    x: jnp.ndarray,            # (B, S, d_model)
    positions: jnp.ndarray,    # (B, S) int32
    cfg: AttentionConfig,
    ctx: ParallelCtx = NO_PARALLEL,
) -> jnp.ndarray:
    """Training / prefill attention over a full sequence (causal)."""
    if cfg.mla is not None:
        return mla_forward(params, x, positions, cfg, ctx)
    B, S, _ = x.shape
    h, kvh, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = (x @ params["wq"]).reshape(B, S, h, d)
    k = _slice_kv((x @ params["wk"]).reshape(B, S, kvh, d), cfg, ctx)
    v = _slice_kv((x @ params["wv"]).reshape(B, S, kvh, d), cfg, ctx)
    cos, sin = cfg.rope(positions, d)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    o = blocked_causal_attention(
        q, k, v, scale=cfg.softmax_scale(d), window=cfg.window, softcap=cfg.softcap,
        q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk, remat_chunks=cfg.remat_query_chunks,
    )
    out = o.reshape(B, S, h * d) @ params["wo"]
    return ctx.psum_tp(out)


def attention_decode(
    params,
    x: jnp.ndarray,            # (B, d_model) — single position
    position: jnp.ndarray,     # () or (B,) int32 — current position (== cache_len)
    cache: dict,               # {"k": (B,S_loc,Hkv,D), "v": ...}
    cfg: AttentionConfig,
    ctx: ParallelCtx = NO_PARALLEL,
):
    """One decode step.  Returns (out (B,d_model), updated cache).

    A ``(B,)`` position decodes each batch row at its own sequence position
    (continuous-batching slots); a scalar decodes the whole batch in lockstep.
    """
    if cfg.mla is not None:
        return mla_decode(params, x, position, cache, cfg, ctx)
    B, _ = x.shape
    h, kvh, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = (x @ params["wq"]).reshape(B, h, d)
    k = (x @ params["wk"]).reshape(B, kvh, d)
    v = (x @ params["wv"]).reshape(B, kvh, d)
    if jnp.ndim(position) == 1:
        cos, sin = cfg.rope(position, d)                        # (B, d/2)
        q = apply_rope(q[:, None], cos[:, None], sin[:, None])[:, 0]
        k = apply_rope(k[:, None], cos[:, None], sin[:, None])[:, 0]
    else:
        cos, sin = cfg.rope(position[None], d)                      # (1, d/2)
        q = apply_rope(q[:, None], cos[None], sin[None])[:, 0]
        k = apply_rope(k[:, None], cos[None], sin[None])[:, 0]

    # cache keeps all local KV heads; when KV is replicated across tp the
    # shard's head is sliced at attention time (cache stays tp-identical)
    eff_len = cache["k"].shape[1] * max(ctx.seq_size, 1)
    if cfg.window is not None and eff_len <= cfg.window:
        # Ring-buffer cache holding exactly the window: eviction enforces the
        # window, so no position mask beyond "slot already written" is needed.
        slot = position % eff_len
        cache = _cache_insert(cache, {"k": k, "v": v}, slot, ctx)
        cache_len = jnp.minimum(position + 1, eff_len)
        win = None
    else:
        cache = _cache_insert(cache, {"k": k, "v": v}, position, ctx)
        cache_len = position + 1
        win = cfg.window
    k_att = _slice_kv(cache["k"], cfg, ctx)
    v_att = _slice_kv(cache["v"], cfg, ctx)
    o = decode_attention(q, k_att, v_att, cache_len, scale=cfg.softmax_scale(d),
                         window=win, softcap=cfg.softcap, ctx=ctx)
    out = o.reshape(B, h * d) @ params["wo"]
    return ctx.psum_tp(out), cache


def _cache_insert(cache: dict, new: dict, position, ctx: ParallelCtx):
    """Insert this step's K/V (or latent) into a (possibly seq-sharded) cache.

    Scalar ``position`` uses a single dynamic_update_slice (whole batch writes
    one seq slot); per-row ``(B,)`` positions scatter each row into its own
    slot via a one-hot select.  Rows whose position falls outside this shard's
    seq range (seq-sharded cache) leave the buffer untouched.
    """
    out = dict(cache)
    per_row = jnp.ndim(position) == 1
    for name, val in new.items():
        buf = cache[name]                      # (B, S_local, ...)
        S_local = buf.shape[1]
        local_pos = position - ctx.seq_index() * S_local
        if per_row:
            hit = jnp.arange(S_local)[None, :] == local_pos[:, None]  # (B, S_local)
            hit = hit.reshape(hit.shape + (1,) * (buf.ndim - 2))
            out[name] = jnp.where(hit, val[:, None].astype(buf.dtype), buf)
        else:
            owner = (local_pos >= 0) & (local_pos < S_local)
            idx = jnp.clip(local_pos, 0, S_local - 1)
            updated = lax.dynamic_update_slice_in_dim(buf, val[:, None].astype(buf.dtype), idx, axis=1)
            out[name] = jnp.where(owner, updated, buf) if ctx.seq_axis is not None else updated
    return out


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2 / V3)
# ---------------------------------------------------------------------------


def _mla_query(params, x):
    """Per-head queries, flattened: through the low-rank bottleneck when the
    config has one (``wq_a -> q_norm -> wq_b``), else ``x @ wq``."""
    if "wq" in params:
        return x @ params["wq"]
    return rmsnorm(params["q_norm"], x @ params["wq_a"]) @ params["wq_b"]


def mla_forward(params, x, positions, cfg: AttentionConfig, ctx: ParallelCtx):
    """MLA training/prefill: expand latent to per-head K/V (naive path)."""
    with scopes.scope(scopes.MLA):
        return _mla_forward(params, x, positions, cfg, ctx)


def _mla_forward(params, x, positions, cfg: AttentionConfig, ctx: ParallelCtx):
    m = cfg.mla
    B, S, _ = x.shape
    h = cfg.n_heads
    qk_dim = m.qk_nope_dim + m.qk_rope_dim

    q = _mla_query(params, x).reshape(B, S, h, qk_dim)

    kv_a = x @ params["wkv_a"]                       # (B,S,rank+rope)
    c_kv = rmsnorm(params["kv_norm"], kv_a[..., : m.kv_lora_rank])
    k_rope = kv_a[..., m.kv_lora_rank:]              # (B,S,rope) shared across heads

    cos, sin = cfg.rope(positions, m.qk_rope_dim)
    q = apply_rope_partial(q, cos, sin, m.qk_rope_dim)
    k_rope = apply_rope(k_rope[:, :, None, :], cos, sin)  # (B,S,1,rope)

    k_nope = (c_kv @ params["wk_b"]).reshape(B, S, h, m.qk_nope_dim)
    v = (c_kv @ params["wv_b"]).reshape(B, S, h, m.v_head_dim)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (B, S, h, m.qk_rope_dim))], axis=-1)

    o = blocked_causal_attention(
        q, k, v, scale=cfg.softmax_scale(qk_dim), softcap=cfg.softcap,
        q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk, remat_chunks=cfg.remat_query_chunks,
    )
    out = o.reshape(B, S, h * m.v_head_dim) @ params["wo"]
    return ctx.psum_tp(out)


def mla_decode(params, x, position, cache, cfg: AttentionConfig, ctx: ParallelCtx):
    """MLA decode with *latent* cache and absorbed projections.

    Cache stores (c_kv, k_rope) only — the paper's memory saving.  Score and
    value computation are done in latent space by absorbing wk_b into the
    query and wv_b into the output (the production DeepSeek decode path).
    """
    m = cfg.mla
    B, _ = x.shape
    h = cfg.n_heads
    rank = m.kv_lora_rank

    q = _mla_query(params, x).reshape(B, h, m.qk_nope_dim + m.qk_rope_dim)
    q_nope, q_rope = q[..., : m.qk_nope_dim], q[..., m.qk_nope_dim:]

    kv_a = x @ params["wkv_a"]
    c_kv = rmsnorm(params["kv_norm"], kv_a[..., :rank])          # (B, rank)
    if jnp.ndim(position) == 1:
        cos, sin = cfg.rope(position, m.qk_rope_dim)                       # (B, rope/2)
        q_rope = apply_rope(q_rope[:, None], cos[:, None], sin[:, None])[:, 0]
        k_rope = apply_rope(kv_a[..., rank:][:, None, None, :], cos[:, None], sin[:, None])[:, 0, 0]
    else:
        cos, sin = cfg.rope(position[None], m.qk_rope_dim)
        q_rope = apply_rope(q_rope[:, None], cos[None], sin[None])[:, 0]
        k_rope = apply_rope(kv_a[..., rank:][:, None, None, :], cos[None], sin[None])[:, 0, 0]

    cache = _cache_insert(cache, {"c_kv": c_kv, "k_rope": k_rope}, position, ctx)

    # Absorb wk_b into q:  q_lat[b,h,r] = sum_d q_nope[b,h,d] * wk_b[r, h*d]
    wk_b = params["wk_b"].reshape(rank, h, m.qk_nope_dim)
    q_lat = jnp.einsum("bhd,rhd->bhr", q_nope.astype(jnp.float32), wk_b.astype(jnp.float32))

    ckv_buf = cache["c_kv"]                                     # (B, S_loc, rank)
    krope_buf = cache["k_rope"]                                 # (B, S_loc, rope)
    S_local = ckv_buf.shape[1]
    shard = ctx.seq_index()
    pos = shard * S_local + jnp.arange(S_local)
    if jnp.ndim(position) == 1:
        valid = pos[None, :] < (position[:, None] + 1)           # (B, S_local)
        vmask = valid[:, None]
    else:
        valid = pos < (position + 1)
        vmask = valid[None, None]

    scale = cfg.softmax_scale(m.qk_nope_dim + m.qk_rope_dim)
    s = jnp.einsum("bhr,bkr->bhk", q_lat, ckv_buf.astype(jnp.float32))
    s += jnp.einsum("bhd,bkd->bhk", q_rope.astype(jnp.float32), krope_buf.astype(jnp.float32))
    s = s * scale
    s = jnp.where(vmask, s, NEG_INF)

    m_local = s.max(axis=-1)
    mx = ctx.pmax_seq(m_local)
    p = jnp.exp(s - mx[..., None])
    l = ctx.psum_seq(p.sum(axis=-1))
    o_lat = ctx.psum_seq(jnp.einsum("bhk,bkr->bhr", p, ckv_buf.astype(jnp.float32)))
    o_lat = o_lat / jnp.maximum(l, 1e-37)[..., None]            # (B,h,rank)

    # Absorb wv_b:  o[b,h,dv] = sum_r o_lat[b,h,r] * wv_b[r, h*dv]
    wv_b = params["wv_b"].reshape(rank, h, m.v_head_dim)
    o = jnp.einsum("bhr,rhd->bhd", o_lat, wv_b.astype(jnp.float32))
    out = o.reshape(B, h * m.v_head_dim).astype(x.dtype) @ params["wo"]
    return ctx.psum_tp(out), cache


# ---------------------------------------------------------------------------
# Cache construction
# ---------------------------------------------------------------------------


def init_attention_cache(batch: int, max_len: int, cfg: AttentionConfig, dtype,
                         seq_shards: int = 1) -> dict:
    """Empty decode cache.  ``max_len`` is the *global* cache length."""
    S_local = max_len // seq_shards
    if cfg.mla is not None:
        m = cfg.mla
        return {
            "c_kv": jnp.zeros((batch, S_local, m.kv_lora_rank), dtype),
            "k_rope": jnp.zeros((batch, S_local, m.qk_rope_dim), dtype),
        }
    return {
        "k": jnp.zeros((batch, S_local, cfg.cache_kv_heads, cfg.head_dim), dtype),
        "v": jnp.zeros((batch, S_local, cfg.cache_kv_heads, cfg.head_dim), dtype),
    }
