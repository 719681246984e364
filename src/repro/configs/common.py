"""Shared helpers for architecture configs, incl. the smoke-test reducer."""

from __future__ import annotations

import dataclasses

from repro.models import (AttentionConfig, LayerSpec, MambaConfig, MLAConfig,
                          ModelConfig, MoEConfig, RWKVConfig)


def smoke_reduce(cfg: ModelConfig) -> ModelConfig:
    """Reduced same-family variant: <=2 periods, d_model<=512, <=4 experts,
    at most one leading dense layer.

    Keeps the pattern (so hybrid/alternating structure is exercised) while
    shrinking every dimension for a CPU-speed forward/train step.
    """
    d_model = 256
    n_layers = len(cfg.pattern) * max(1, 2 // len(cfg.pattern))
    kw: dict = {
        "n_layers": n_layers,
        "d_model": d_model,
        "d_ff": 512,
        "vocab_size": min(cfg.vocab_size, 512),
        "param_dtype": "float32",
        "compute_dtype": "float32",
    }
    if cfg.attn is not None:
        a = cfg.attn
        n_heads = 4
        n_kv = max(1, min(a.n_kv_heads, n_heads * a.n_kv_heads // a.n_heads)) or 1
        mla = None
        if a.mla is not None:
            mla = MLAConfig(q_lora_rank=None if a.mla.q_lora_rank is None else 64,
                            kv_lora_rank=32, qk_nope_dim=32, qk_rope_dim=16,
                            v_head_dim=32)
        kw["attn"] = dataclasses.replace(
            a, n_heads=n_heads, n_kv_heads=max(n_kv, 1), head_dim=64,
            window=None if a.window is None else 64,
            mla=mla, q_chunk=64, kv_chunk=64)
        # shrink per-layer window overrides in the pattern
        kw["pattern"] = tuple(
            dataclasses.replace(s, window=None if s.window is None else 64)
            for s in cfg.pattern)
    if cfg.moe is not None:
        kw["moe"] = dataclasses.replace(
            cfg.moe, n_experts=4, n_routed=None, first_expert=0,
            top_k=min(cfg.moe.top_k, 2), d_ff=256)
    if cfg.first_k_dense:
        kw["first_k_dense"] = 1
    if cfg.mamba is not None:
        kw["mamba"] = dataclasses.replace(cfg.mamba, d_state=8, chunk=32)
    if cfg.rwkv is not None:
        kw["rwkv"] = dataclasses.replace(cfg.rwkv, head_dim=32, decay_lora=16,
                                         mix_lora=8, chunk=16)
    if cfg.prefix_len > 0:
        kw["prefix_len"] = 8
    return cfg.replace(**kw)
