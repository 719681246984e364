"""Distributed serving: one-token decode (serve_step) on the refined mesh.

Layout (per DESIGN.md): decode is latency-bound, so the ``model`` axis is
used mostly for tensor parallelism (stage=1 when the head count allows);
architectures whose head count caps tp keep a short pipeline and stream the
local batch through it in groups (same circular ppermute pattern as
training).  The KV/state cache is sharded:

* batch over ``(pod, data)`` for the throughput decode shapes,
* **sequence-sharded over ``data``** for ``long_500k`` (batch 1): each shard
  owns a slice of the KV cache and attention combines partial softmaxes with
  pmax/psum — flash-decoding mapped onto the mesh.

``check_vma=False``: decode caches are deliberately replicated across tp
when KV heads < tp, which the vma checker cannot express.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.planner import serve_stage_candidates
from repro.distributed.mesh import MeshPlan, mesh_plan, refine_mesh
from repro.distributed.sharding import (Layout, SERVE_LAYOUT, named,
                                        param_pspecs, state_pspecs)
from repro.models.blocks import decode_periods, init_period_states, shard_config
from repro.models.config import ModelConfig
from repro.models.norms import rmsnorm
from repro.models.module import vary_all

from .pipeline import make_ctx, pad_periods
from .train import pad_vocab_params, prepare_params
from .vocab_parallel import vp_embed


def serve_head_count(cfg: ModelConfig) -> int:
    """Head count that caps tensor parallelism for decode."""
    return cfg.attn.n_heads if cfg.attn is not None else (
        cfg.d_model // cfg.rwkv.head_dim if cfg.rwkv is not None else 1)


def pick_serve_stage(cfg: ModelConfig, model_axis: int) -> int:
    """Serve prefers TP: the smallest stage count whose tp divides the query
    head count (query heads must shard; KV may replicate).  Candidates are
    the divisors of ``model_axis`` — not a fixed power-of-two probe — so a
    6-device model axis yields stage 2 (tp 3) rather than a 6-deep
    pipeline.  ``core.planner.plan_serve`` makes the full latency-priced
    choice; this is the profile-free default."""
    return serve_stage_candidates(model_axis, serve_head_count(cfg))[0]


@dataclasses.dataclass(frozen=True)
class ServeSpec:
    cfg: ModelConfig
    plan: MeshPlan
    cache_len: int
    batch_global: int
    seq_shard: bool            # long-context: shard cache seq over 'data'
    n_groups: int = 1          # decode pipelining groups (stage > 1)
    # Heterogeneous decode slots per dp shard (the ``TrainSpec.shard_alloc``
    # counterpart): shard d serves shard_alloc[d] live slots, every shard is
    # padded to max(shard_alloc) rows (SPMD needs equal local shapes) and the
    # padding rows are masked out of the sampling head.  Setting this also
    # switches the step to the per-slot signature
    # ``fn(params, token (B,), position (B,), reset (B,), states)``.
    shard_alloc: tuple[int, ...] | None = None

    @property
    def batch_sharded(self) -> bool:
        return not self.seq_shard

    @property
    def per_slot(self) -> bool:
        return self.shard_alloc is not None

    @property
    def slot_mask(self):
        """(dp_shards, B_max) validity of each padded slot row."""
        assert self.shard_alloc is not None
        b_max = self.batch_global // self.plan.dp_shards
        return jnp.asarray([[i < y for i in range(b_max)]
                            for y in self.shard_alloc])

    @property
    def cfg_local(self) -> ModelConfig:
        ep = self.plan.data if self.batch_sharded else 1
        return shard_config(self.cfg, tp=self.plan.tp, ep=ep)


def spmd_decode_fn(spec: ServeSpec):
    cfg = spec.cfg
    cfg_local = spec.cfg_local
    plan = spec.plan
    P_st = plan.stage
    ctx = make_ctx(plan, ep=spec.batch_sharded, seq_shard=spec.seq_shard)

    def head_w(params, cb=None):
        if cfg.tie_embeddings:
            w = params["embed"]
            return (w[cb] if cb is not None else w).T
        w = params["head"]
        return w[cb] if cb is not None else w

    slot_mask = spec.slot_mask if spec.per_slot else None

    def body(params, token, position, states):
        # token: (B_loc,) or (B_loc, CB); position: () or (B_loc,) int32
        if cfg.n_codebooks > 1:
            x = sum(vp_embed(params["embed"][cb], token[:, cb], ctx)
                    for cb in range(cfg.n_codebooks))
        else:
            x = vp_embed(params["embed"], token, ctx)
        if cfg.embed_scale:
            x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
        x = x.astype(cfg.cdtype)

        if P_st == 1:
            h, new_states = decode_periods(params["periods"], x, position,
                                           states, cfg_local, ctx)
        else:
            h, new_states = _pipelined_decode(params["periods"], x, position,
                                              states, cfg_local, ctx, P_st,
                                              spec.n_groups)

        h = rmsnorm(params["final_norm"], h, cfg.norm_eps, cfg.zero_centered_norm)
        if cfg.n_codebooks > 1:
            logits = jnp.stack([(h @ head_w(params, cb)).astype(jnp.float32)
                                for cb in range(cfg.n_codebooks)], axis=1)
        else:
            logits = (h @ head_w(params)).astype(jnp.float32)
        if cfg.logit_softcap is not None:
            logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
        if P_st > 1:
            stage = lax.axis_index("stage")
            logits = lax.psum(
                jnp.where(stage == P_st - 1, logits, jnp.zeros_like(logits)),
                "stage")
        return logits, new_states

    if not spec.per_slot:
        return body

    def slot_fn(params, token, position, reset, states):
        # per-slot decode: position/reset are (B_loc,); padded slot rows
        # (beyond this shard's shard_alloc count) are masked out of the
        # sampling head, and reset rows get their recurrent state zeroed
        # before the step (attention caches need no reset — the per-row
        # cache_len mask hides stale entries).
        dp_idx = lax.axis_index("pod") * plan.data + lax.axis_index("data")
        valid = lax.dynamic_index_in_dim(slot_mask, dp_idx, 0, keepdims=False)

        def clear(s):
            r = reset.reshape((1, -1) + (1,) * (s.ndim - 2))
            return jnp.where(r, jnp.zeros_like(s), s)

        states = jax.tree.map(clear, states)
        logits, new_states = body(params, token, position, states)
        vmask = valid.reshape((-1,) + (1,) * (logits.ndim - 1))
        logits = jnp.where(vmask, logits, jnp.zeros_like(logits))
        return logits, new_states

    return slot_fn


def _pipelined_decode(periods_local, x, position, states, cfg_local, ctx,
                      P_st: int, n_groups: int):
    """Stream the local batch through the stage pipeline in groups."""
    B_loc, D = x.shape
    n_g = n_groups if (B_loc % n_groups == 0 and B_loc >= n_groups) else 1
    bg = B_loc // n_g
    xg = x.reshape(n_g, bg, D)
    stage = lax.axis_index("stage")
    perm = [(i, (i + 1) % P_st) for i in range(P_st)]

    def slice_b(s, g):
        return lax.dynamic_slice_in_dim(s, g * bg, bg, axis=1)

    def slice_pos(g):
        # per-row positions travel with their batch group
        if jnp.ndim(position) == 1:
            return lax.dynamic_slice_in_dim(position, g * bg, bg)
        return position

    def update_b(s, new, g, active):
        upd = lax.dynamic_update_slice_in_dim(s, new.astype(s.dtype), g * bg, axis=1)
        return jnp.where(active, upd, s)

    carry0 = vary_all((jnp.zeros((bg, D), x.dtype),
                       jnp.zeros((n_g, bg, D), x.dtype), states))

    def tick(carry, t):
        act, outs, st = carry
        g = jnp.clip(t - stage, 0, n_g - 1)
        inp = jnp.where(stage == 0,
                        lax.dynamic_index_in_dim(xg, jnp.clip(t, 0, n_g - 1), 0,
                                                 keepdims=False),
                        act)
        st_g = jax.tree.map(lambda s: slice_b(s, g), st)
        h, st_new = decode_periods(periods_local, inp, slice_pos(g), st_g,
                                   cfg_local, ctx)
        active = (t >= stage) & (t < stage + n_g)
        st = jax.tree.map(lambda s, n: update_b(s, n, g, active), st, st_new)
        nxt = lax.ppermute(h, "stage", perm)
        oidx = t - (P_st - 1)
        outs = jnp.where(
            (stage == P_st - 1) & (oidx >= 0),
            lax.dynamic_update_index_in_dim(outs, h, jnp.clip(oidx, 0, n_g - 1), 0),
            outs)
        return vary_all((nxt, outs, st)), None

    (_, outs, states), _ = lax.scan(tick, carry0, jnp.arange(n_g + P_st - 1))
    # outputs valid on the last stage; broadcast to all stages so the head
    # can run (masked psum keeps only the real values)
    return outs.reshape(B_loc, D), states


# ---------------------------------------------------------------------------
# Prefill (inference over a full prompt)
# ---------------------------------------------------------------------------


def build_prefill_step(cfg: ModelConfig, production_mesh: Mesh, *,
                       batch_global: int, seq_len: int,
                       stage: int | None = None, n_micro: int | None = None):
    """Prefill: forward over the prompt through the HPP pipeline, returning
    last-position logits.  (KV-cache export is an output-layout detail with
    no FLOPs — see DESIGN.md §Dry-run notes.)"""
    from repro.distributed.mesh import pick_stage_count
    from repro.runtime.pipeline import (TrainSpec, batch_pspecs, pipeline_apply,
                                        spmd_loss_fn)
    from repro.runtime.train import default_n_micro

    n_heads = cfg.attn.n_heads if cfg.attn is not None else (
        cfg.d_model // cfg.rwkv.head_dim if cfg.rwkv is not None else cfg.d_model)
    model_axis = production_mesh.shape["model"]
    if stage is None:
        stage = pick_stage_count(cfg.n_layers, len(cfg.pattern), model_axis,
                                 n_heads)
    mesh = refine_mesh(production_mesh, stage)
    plan = mesh_plan(production_mesh, stage)
    if n_micro is None:
        n_micro = default_n_micro(cfg, plan, batch_global)
    spec = TrainSpec(cfg=cfg, plan=plan, n_micro=n_micro, remat=False)
    cfg_local = spec.cfg_local
    ctx = make_ctx(plan)
    M = n_micro

    def head_w(params, cb=None):
        if cfg.tie_embeddings:
            w = params["embed"]
            return (w[cb] if cb is not None else w).T
        w = params["head"]
        return w[cb] if cb is not None else w

    def fn(params, batch):
        tokens = batch["tokens"]
        B_loc = tokens.shape[0]
        mb = B_loc // M
        if cfg.n_codebooks > 1:
            x = sum(vp_embed(params["embed"][cb], tokens[:, cb], ctx)
                    for cb in range(cfg.n_codebooks))
        else:
            x = vp_embed(params["embed"], tokens, ctx)
        if cfg.embed_scale:
            x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
        x = x.astype(cfg.cdtype)
        if cfg.prefix_len > 0:
            px = batch["prefix"].astype(cfg.cdtype) @ params["prefix_proj"]
            x = jnp.concatenate([px.astype(cfg.cdtype), x], axis=1)
        S_tot = x.shape[1]
        positions = jnp.broadcast_to(jnp.arange(S_tot, dtype=jnp.int32),
                                     (mb, S_tot))
        n_periods = cfg.n_periods
        padded = -(-n_periods // plan.stage) * plan.stage
        k_per = padded // plan.stage
        mask_global = jnp.asarray([1.0] * n_periods +
                                  [0.0] * (padded - n_periods), jnp.float32)
        if plan.stage > 1:
            mask_local = lax.dynamic_slice_in_dim(
                mask_global, lax.axis_index("stage") * k_per, k_per)
        else:
            mask_local = mask_global
        x_micro = x.reshape(M, mb, S_tot, cfg.d_model)
        from repro.runtime.pipeline import pipeline_apply as _pa
        outs, _ = _pa(params["periods"], mask_local, x_micro, positions,
                      cfg_local, ctx, plan.stage, remat=False)
        h_last = outs[:, :, -1, :].reshape(B_loc, cfg.d_model)
        if plan.stage > 1:
            st = lax.axis_index("stage")
            h_last = lax.psum(
                jnp.where(st == plan.stage - 1, h_last, jnp.zeros_like(h_last)),
                "stage")
        h_last = rmsnorm(params["final_norm"], h_last, cfg.norm_eps,
                         cfg.zero_centered_norm)
        if cfg.n_codebooks > 1:
            logits = jnp.stack([(h_last @ head_w(params, cb)).astype(jnp.float32)
                                for cb in range(cfg.n_codebooks)], axis=1)
        else:
            logits = (h_last @ head_w(params)).astype(jnp.float32)
        if cfg.logit_softcap is not None:
            logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
        return logits

    kv_repl = cfg.attn is not None and cfg.attn.n_kv_heads % plan.tp != 0
    layout = dataclasses.replace(SERVE_LAYOUT, kv_replicated=kv_repl,
                                 ep_axis="data")
    abstract_p = jax.eval_shape(lambda k: prepare_params(k, cfg, plan),
                                jax.random.PRNGKey(0))
    pspecs = param_pspecs(abstract_p, layout)
    bspecs = batch_pspecs(cfg)
    logits_spec = P(("pod", "data"), "tp") if cfg.n_codebooks == 1 \
        else P(("pod", "data"), None, "tp")
    sharded = jax.shard_map(fn, mesh=mesh, in_specs=(pspecs, bspecs),
                        out_specs=logits_spec, check_vma=False)
    step = jax.jit(sharded, in_shardings=(named(mesh, pspecs),
                                          named(mesh, bspecs)))
    return ServeStep(spec=ServeSpec(cfg, plan, seq_len, batch_global, False,
                                    n_micro),
                     mesh=mesh, param_specs=pspecs, state_specs=bspecs,
                     step_fn=step)


# ---------------------------------------------------------------------------
# Builder
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ServeStep:
    spec: ServeSpec
    mesh: Mesh
    param_specs: object
    state_specs: object
    step_fn: object


def prepare_serve_states(cfg: ModelConfig, plan: MeshPlan, batch_global: int,
                         cache_len: int):
    """GLOBAL decode state tree (periods padded to the stage count)."""
    padded = -(-cfg.n_periods // plan.stage) * plan.stage
    cfg_pad = cfg.replace(n_layers=padded * len(cfg.pattern))
    return init_period_states(batch_global, cache_len, cfg_pad, cfg.cdtype)


def build_serve_step(cfg: ModelConfig, production_mesh: Mesh, *,
                     batch_global: int, cache_len: int,
                     stage: int | None = None, seq_shard: bool = False,
                     n_groups: int | None = None) -> ServeStep:
    model_axis = production_mesh.shape["model"]
    if stage is None:
        stage = pick_serve_stage(cfg, model_axis)
    mesh = refine_mesh(production_mesh, stage)
    plan = mesh_plan(production_mesh, stage)
    if n_groups is None:
        b_loc = batch_global // plan.dp_shards if not seq_shard else batch_global
        n_groups = stage if (b_loc % stage == 0 and b_loc >= stage) else 1
    spec = ServeSpec(cfg=cfg, plan=plan, cache_len=cache_len,
                     batch_global=batch_global, seq_shard=seq_shard,
                     n_groups=n_groups)

    kv_repl = cfg.attn is not None and cfg.attn.n_kv_heads % plan.tp != 0
    # batch-sharded decode keeps expert parallelism over 'data' (EP=DP);
    # seq-sharded long-context decode replicates experts (data carries the
    # KV sequence shards instead)
    layout = dataclasses.replace(SERVE_LAYOUT, kv_replicated=kv_repl,
                                 ep_axis=None if seq_shard else "data",
                                 seq_axis="data" if seq_shard else None)

    abstract_p = jax.eval_shape(lambda k: prepare_params(k, cfg, plan),
                                jax.random.PRNGKey(0))
    pspecs = param_pspecs(abstract_p, layout)
    abstract_s = jax.eval_shape(
        lambda: prepare_serve_states(cfg, plan, batch_global, cache_len))
    sspecs = state_pspecs(abstract_s, layout, batch_sharded=not seq_shard)

    tok_spec = (P(("pod", "data")) if not seq_shard else P(None)) \
        if cfg.n_codebooks == 1 else \
        (P(("pod", "data"), None) if not seq_shard else P(None, None))
    logits_spec = P(("pod", "data"), "tp") if not seq_shard else P(None, "tp")
    if cfg.n_codebooks > 1:
        logits_spec = P(("pod", "data"), None, "tp") if not seq_shard \
            else P(None, None, "tp")

    fn = spmd_decode_fn(spec)
    sharded = jax.shard_map(fn, mesh=mesh,
                        in_specs=(pspecs, tok_spec, P(), sspecs),
                        out_specs=(logits_spec, sspecs),
                        check_vma=False)
    step = jax.jit(sharded,
                   in_shardings=(named(mesh, pspecs),
                                 named(mesh, tok_spec),
                                 named(mesh, P()),
                                 named(mesh, sspecs)))
    return ServeStep(spec=spec, mesh=mesh, param_specs=pspecs,
                     state_specs=sspecs, step_fn=step)


def build_slot_serve_step(cfg: ModelConfig, production_mesh: Mesh, *,
                          cache_len: int, shard_alloc,
                          stage: int | None = None,
                          n_groups: int | None = None) -> ServeStep:
    """Continuous-batching decode step with heterogeneous slot splits.

    ``shard_alloc[d]`` live decode slots run on dp shard ``d`` (a planner
    ``ServePlan.shard_alloc``, or any unbalanced split).  Every shard is
    padded to ``B_max = max(shard_alloc)`` rows; the returned step is

        ``step_fn(params, token (B,), position (B,), reset (B,), states)``

    with ``B = dp_shards * B_max`` global padded rows in shard-major order
    (rows ``[d*B_max, d*B_max + shard_alloc[d])`` are live).  ``position``
    is per-row — each slot decodes at its own sequence position — and rows
    with ``reset`` set have their recurrent state zeroed before the step
    (slot admission).  Padded rows return zero logits.
    """
    model_axis = production_mesh.shape["model"]
    if stage is None:
        stage = pick_serve_stage(cfg, model_axis)
    mesh = refine_mesh(production_mesh, stage)
    plan = mesh_plan(production_mesh, stage)
    shard_alloc = tuple(int(y) for y in shard_alloc)
    assert len(shard_alloc) == plan.dp_shards, (shard_alloc, plan.dp_shards)
    assert max(shard_alloc) >= 1, shard_alloc
    b_max = max(shard_alloc)
    batch_global = b_max * plan.dp_shards
    if n_groups is None:
        n_groups = stage if (b_max % stage == 0 and b_max >= stage) else 1
    spec = ServeSpec(cfg=cfg, plan=plan, cache_len=cache_len,
                     batch_global=batch_global, seq_shard=False,
                     n_groups=n_groups, shard_alloc=shard_alloc)

    kv_repl = cfg.attn is not None and cfg.attn.n_kv_heads % plan.tp != 0
    layout = dataclasses.replace(SERVE_LAYOUT, kv_replicated=kv_repl,
                                 ep_axis="data")

    abstract_p = jax.eval_shape(lambda k: prepare_params(k, cfg, plan),
                                jax.random.PRNGKey(0))
    pspecs = param_pspecs(abstract_p, layout)
    abstract_s = jax.eval_shape(
        lambda: prepare_serve_states(cfg, plan, batch_global, cache_len))
    sspecs = state_pspecs(abstract_s, layout, batch_sharded=True)

    row_spec = P(("pod", "data"))
    tok_spec = row_spec if cfg.n_codebooks == 1 else P(("pod", "data"), None)
    logits_spec = P(("pod", "data"), "tp") if cfg.n_codebooks == 1 \
        else P(("pod", "data"), None, "tp")

    fn = spmd_decode_fn(spec)
    sharded = jax.shard_map(fn, mesh=mesh,
                        in_specs=(pspecs, tok_spec, row_spec, row_spec,
                                  sspecs),
                        out_specs=(logits_spec, sspecs),
                        check_vma=False)
    step = jax.jit(sharded,
                   in_shardings=(named(mesh, pspecs),
                                 named(mesh, tok_spec),
                                 named(mesh, row_spec),
                                 named(mesh, row_spec),
                                 named(mesh, sspecs)))
    return ServeStep(spec=spec, mesh=mesh, param_specs=pspecs,
                     state_specs=sspecs, step_fn=step)