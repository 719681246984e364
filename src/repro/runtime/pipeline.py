"""The HPP training runtime: circular pipeline under shard_map.

Asteroid's hybrid pipeline parallelism on the refined TPU mesh
``(pod, data, stage, tp)``:

* the decoder body (stacked periods) is sharded over ``stage``; each tick of
  a ``lax.scan`` executes one stage forward on one micro-batch and
  ``ppermute``s the activation to the next stage (M + P - 1 ticks for M
  micro-batches) — jax.grad of the scan yields the reverse pipeline;
* intra-stage parallelism = data parallelism over ``(pod, data)`` plus
  Megatron tensor parallelism over ``tp`` (explicit psums inside layers);
  Algorithm 1's *heterogeneous* sample allocation is realized by padding
  every data shard's micro-batch to ``B_max = max_d y_d``
  (``TrainSpec.shard_alloc``, packed host-side by ``data.pack_batch``) with
  a static validity mask weighting the loss reduction by true counts;
* MoE experts are expert-parallel over ``data`` (all_to_all dispatch);
* embedding and LM head are vocab-parallel over ``tp``; after the pipeline,
  last-stage outputs are *redistributed across stages* so the CE/head work
  is stage-sharded instead of wasted;
* the stage body is remat'ed (`jax.checkpoint`), bounding resident
  activations to the stage *input* per in-flight micro-batch — the SPMD
  realization of the paper's O(K_p) 1F1B memory bound (DESIGN.md §4).

The paper's planner picks the stage count; ``pad_periods`` pads the period
stack with zero (identity) layers when stages don't divide the period count.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro import scopes
from repro.core.schedule import scan_ticks
from repro.kernels.quant_transfer import dequantize_op, quantize_op
from repro.distributed.mesh import MeshPlan
from repro.models.blocks import apply_period, shard_config
from repro.models.config import ModelConfig
from repro.models.model import MTP_WEIGHT
from repro.models.module import ParallelCtx, pcast_varying, vary_all
from repro.models.norms import rmsnorm

from .vocab_parallel import vp_chunked_ce, vp_embed


def make_ctx(plan: MeshPlan, ep: bool = True, seq_shard: bool = False) -> ParallelCtx:
    # axes are always named (size-1 collectives are free) so vma typing stays
    # uniform across layouts
    return ParallelCtx(
        tp_axis="tp", tp_size=plan.tp,
        ep_axis="data" if ep else None, ep_size=plan.data,
        dp_axes=("pod", "data"),
        seq_axis="data" if seq_shard else None,
        seq_size=plan.data if seq_shard else 1,
    )


def pad_periods(periods, n_periods: int, n_stages: int):
    """Pad stacked period params with zero (identity) periods to a multiple
    of n_stages.  Returns (padded_params, valid_mask (padded,))."""
    padded = -(-n_periods // n_stages) * n_stages
    pad = padded - n_periods
    if pad == 0:
        return periods, jnp.ones((n_periods,), jnp.float32)
    padded_params = jax.tree.map(
        lambda x: jnp.concatenate(
            [x, jnp.zeros((pad, *x.shape[1:]), x.dtype)], axis=0),
        periods)
    mask = jnp.concatenate([jnp.ones((n_periods,)), jnp.zeros((pad,))]).astype(jnp.float32)
    return padded_params, mask


def stage_period_mask(stage_periods) -> list[float]:
    """Static validity mask for heterogeneously-split periods: stage p's
    uniform slice holds (j_p - i_p) real periods then zero padding."""
    k = max(j - i for i, j in stage_periods)
    mask: list[float] = []
    for i, j in stage_periods:
        mask += [1.0] * (j - i) + [0.0] * (k - (j - i))
    return mask


def slot_counts(spec: TrainSpec) -> tuple[int, int]:
    """Period x micro-batch slots of one step: ``(real, computed)``.

    Real slots are the model's periods times the micro-batches.  Every
    stage computes its padded share ``k`` of the period stack (the largest
    stage's, ``arrange_periods``/``pad_periods``) on every tick of the scan,
    filled or not, so the chips compute ``stages x k x ticks`` slots."""
    n_stages = spec.plan.stage
    if spec.stage_periods is not None:
        k = max(j - i for i, j in spec.stage_periods)
    else:
        k = -(-spec.cfg.n_periods // n_stages)
    ticks = scan_ticks(n_stages, spec.n_micro, spec.double_buffer)
    return spec.cfg.n_periods * spec.n_micro, n_stages * k * ticks


def arrange_periods(periods, stage_periods):
    """Arrange stacked period params for a planner-chosen (possibly
    heterogeneous) stage split.

    ``stage_periods``: per-stage period ranges [i, j) partitioning
    [0, n_periods).  Stage p's uniform slice [p*k, (p+1)*k) of the result
    (k = max range length) holds its assigned periods followed by zero
    (identity) periods, so the runtime's static per-stage slicing realizes
    the heterogeneous split.  Returns (arranged_params, valid_mask (P*k,)).
    """
    mask_vals = stage_period_mask(stage_periods)
    take = []
    k = max(j - i for i, j in stage_periods)
    for i, j in stage_periods:
        take += list(range(i, j)) + [0] * (k - (j - i))
    idx = jnp.asarray(take)
    mask = jnp.asarray(mask_vals, jnp.float32)

    def f(x):
        g = x[idx]
        keep = (mask > 0).reshape(-1, *([1] * (g.ndim - 1)))
        return jnp.where(keep, g, jnp.zeros_like(g))

    return jax.tree.map(f, periods), mask


# ---------------------------------------------------------------------------
# Stage body
# ---------------------------------------------------------------------------


def _stage_fn(periods_local, period_mask_local, x, positions, cfg_local,
              ctx: ParallelCtx, remat: bool):
    """Apply this stage's local periods (scan), masking padded periods' aux."""

    def body(carry, inputs):
        h, aux = carry
        pp, valid = inputs
        h, a = apply_period(pp, h, positions, cfg_local, ctx)
        return vary_all((h, aux + a * valid)), None

    fn = jax.checkpoint(body) if remat else body
    with scopes.scope(scopes.STAGE):
        # params are stage-varying (and MoE aux data-varying), so the carry
        # is typed varying over all manual axes
        (x, aux) = vary_all((x, jnp.zeros((), jnp.float32)))
        (x, aux), _ = lax.scan(fn, (x, aux),
                               (periods_local, period_mask_local))
    return x, aux


# ---------------------------------------------------------------------------
# Compressed boundary transfer (DESIGN.md §10)
# ---------------------------------------------------------------------------


@partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def compressed_ppermute(x, perm, fmt: str, tile: int):
    """quantize → ppermute → dequantize over the ``stage`` axis.

    The wire moves the packed int8/fp8 payload + per-tile scales instead of
    full-precision activations ((8 + 32/tile)/32 of the fp32 bytes).  The
    custom VJP quantizes the backward cotangent the same way and routes it
    through the *inverse* permutation — exactly the transpose of ppermute,
    so the reverse pipeline's boundary transfers are compressed too.  The
    carried value stays full precision (quantization error enters once per
    hop, not cumulatively), and all-zero tiles (pipeline warm-up bubbles)
    round-trip exactly.
    """
    packed = quantize_op(x, fmt=fmt, tile=tile)
    arrived = {k: lax.ppermute(v, "stage", perm) for k, v in packed.items()}
    return dequantize_op(arrived, x.shape, x.dtype, tile=tile)


def _cperm_fwd(x, perm, fmt, tile):
    # no residuals: the cotangent has the primal's shape/dtype already
    return compressed_ppermute(x, perm, fmt, tile), None


def _cperm_bwd(perm, fmt, tile, _res, g):
    inv = tuple((d, s) for s, d in perm)
    packed = quantize_op(g, fmt=fmt, tile=tile)
    arrived = {k: lax.ppermute(v, "stage", inv) for k, v in packed.items()}
    return (dequantize_op(arrived, g.shape, g.dtype, tile=tile),)


compressed_ppermute.defvjp(_cperm_fwd, _cperm_bwd)


# ---------------------------------------------------------------------------
# Circular pipeline
# ---------------------------------------------------------------------------


def pipeline_apply(periods_local, period_mask_local, x_micro, positions,
                   cfg_local: ModelConfig, ctx: ParallelCtx, n_stages: int,
                   remat: bool = True, double_buffer: bool = False,
                   compress: str = "none", quant_tile: int = 256):
    """Run M micro-batches through the stage pipeline.

    x_micro: (M, mb, S, D) — identical on every stage (batch-sharded over
    dp axes only); returns (outs (M, mb, S, D) valid on the last stage,
    aux_loss — sum over this stage's real ticks).

    ``double_buffer=False`` is the synchronous pipeline: each tick computes
    a stage forward and then ``ppermute``s the output, so the boundary
    transfer of micro-batch *m* serializes with the compute of *m+1* on the
    critical path (M + P - 1 ticks, 1-tick stage hop).

    ``double_buffer=True`` is the overlapped pipeline (DESIGN.md §8): the
    scan carries a (send, recv) buffer pair and each tick (a) launches the
    ppermute of the *previous* tick's output and (b) computes on the input
    received the tick before — the two are data-independent inside the scan
    body, so XLA's scheduler can run the transfer of micro-batch *m* on the
    comm stream while *m+1* computes.  The stage hop becomes 2 ticks
    (compute tick, then an in-flight tick), so the scan runs
    M + 2(P - 1) ticks; per-micro-batch values are bit-identical to the
    synchronous pipeline (same ops, same order — only the tick a transfer
    occupies moves).
    """
    M = x_micro.shape[0]
    P_st = n_stages
    # P_st == 1 runs the same tick scan (M ticks, identity ppermute): a
    # single stage is exactly the degenerate case of the circular pipeline.
    # It has no boundary transfers to hide, so double buffering
    # degenerates to the synchronous scan.
    if P_st == 1:
        double_buffer = False
    stage = lax.axis_index("stage")
    perm = tuple((i, (i + 1) % P_st) for i in range(P_st))
    hop = 2 if double_buffer else 1

    def boundary(x):
        with scopes.scope(scopes.BOUNDARY):
            if compress != "none" and P_st > 1:
                return compressed_ppermute(x, perm, compress, quant_tile)
            return lax.ppermute(x, "stage", perm)

    state0, outs0, aux0 = vary_all(
        (jnp.zeros_like(x_micro[0]), jnp.zeros_like(x_micro),
         jnp.zeros((), jnp.float32)))

    def compute(recv, outs, aux, t):
        """One stage forward on this tick's input; masked aux/outs update.

        Shared by both pipeline variants: only *when* the boundary transfer
        runs differs, never the per-micro-batch math (the staleness-0
        bit-identity contract, ``dist_selftest --async``).
        """
        inp = jnp.where(stage == 0,
                        lax.dynamic_index_in_dim(x_micro, jnp.clip(t, 0, M - 1),
                                                 0, keepdims=False),
                        recv)
        out, a = _stage_fn(periods_local, period_mask_local, inp, positions,
                           cfg_local, ctx, remat)
        # only ticks carrying a real micro-batch contribute aux loss
        valid = (t >= hop * stage) & (t < hop * stage + M)
        aux = aux + jnp.where(valid, a, 0.0)
        oidx = t - hop * (P_st - 1)
        outs = jnp.where(
            (stage == P_st - 1) & (oidx >= 0),
            lax.dynamic_update_index_in_dim(outs, out, jnp.clip(oidx, 0, M - 1), 0),
            outs)
        return out, outs, aux

    if double_buffer:
        def tick(carry, t):
            send, recv, outs, aux = carry
            # transfer of the PREVIOUS tick's output: independent of this
            # tick's compute, so the two streams overlap
            arrived = boundary(send)
            out, outs, aux = compute(recv, outs, aux, t)
            return vary_all((out, arrived, outs, aux)), None

        carry0 = vary_all((state0, state0, outs0, aux0))
    else:
        def tick(carry, t):
            state, outs, aux = carry
            out, outs, aux = compute(state, outs, aux, t)
            nxt = boundary(out)
            return vary_all((nxt, outs, aux)), None

        carry0 = (state0, outs0, aux0)

    with scopes.scope(scopes.PIPELINE):
        final, _ = lax.scan(tick, carry0,
                            jnp.arange(scan_ticks(P_st, M, double_buffer)))
    outs, aux = final[-2], final[-1]
    return outs, aux


# ---------------------------------------------------------------------------
# Full SPMD loss (runs inside shard_map)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TrainSpec:
    """Static configuration of the distributed train step."""

    cfg: ModelConfig                  # GLOBAL model config
    plan: MeshPlan
    n_micro: int
    remat: bool = True
    ce_chunk: int = 1024
    # Planner-lowered heterogeneous stage split: per-stage period ranges
    # [i, j) partitioning [0, n_periods) (core.lowering).  None = uniform.
    stage_periods: tuple[tuple[int, int], ...] | None = None
    # Planner-lowered heterogeneous intra-stage allocation (Algorithm 1 via
    # core.lowering.lower_micro_alloc): per-data-shard samples per
    # micro-batch, summing to the global micro-batch.  The batch arrives
    # packed (data.pack_batch): every shard padded to B_max = max_d y_d,
    # and a static validity mask keeps the padding out of the loss, so the
    # loss/gradient all-reduces are weighted by true per-shard counts.
    # None = uniform dp split (legacy layout, no padding).
    shard_alloc: tuple[int, ...] | None = None
    # Perf iteration 1 (EXPERIMENTS.md): hoist replicated->varying casts
    # (and hence the gradient all-reduces their transposes create) out of
    # the pipeline loops.  False reproduces the paper-faithful baseline.
    hoist_varying: bool = True
    # Async 1F1B runtime (DESIGN.md §8).  ``staleness`` bounds how many
    # rounds a gradient may lag its application: 0 = synchronous semantics
    # (round r's gradients are applied before round r+1 computes), 1 = the
    # optimizer update for round r's gradients happens at the r+1 boundary
    # while round r+1 computes on the pre-update params, so the gradient
    # AllReduce has a full round to hide in.  The knob changes only the
    # step *assembly* (runtime.train); the loss/grad functions are
    # staleness-free.
    staleness: int = 0
    # Double-buffer the stage-boundary sends: the P2P transfer of
    # micro-batch m overlaps the compute of m+1 on a second stream instead
    # of serializing inside the tick (2-tick stage hop, M + 2(P-1) ticks).
    # Per-micro-batch math is unchanged — gradients stay bit-identical to
    # the synchronous pipeline.
    double_buffer: bool = False
    # Compressed transfers (DESIGN.md §10): "none" | "int8" | "fp8".  When
    # set, stage-boundary ppermutes move quantized payloads (per-tile
    # scales, ``quant_tile`` elements per scale) in both directions, and
    # the gradient AllReduce switches to the bucketed/compressed path in
    # runtime.train (size-bounded buckets, per-bucket psum, quantized
    # local contributions with an error-feedback accumulator).
    compress: str = "none"
    quant_tile: int = 256
    # Gradient-bucket size bound in MiB; None = one bucket per free-axes
    # group.  Setting it (without compress) still enables DDP-style
    # bucketed psums so partial syncs overlap the backward.
    bucket_mb: float | None = None
    # Carry the per-bucket quantization residual across steps so the
    # transmitted gradient stream is unbiased (bias -> 0 as 1/T).
    error_feedback: bool = True

    @property
    def bucketed(self) -> bool:
        """True when the gradient path uses explicit per-bucket psums (and
        the step functions thread an error-feedback pytree)."""
        return self.compress != "none" or self.bucket_mb is not None

    @property
    def cfg_local(self) -> ModelConfig:
        return shard_config(self.cfg, tp=self.plan.tp, ep=self.plan.data)


def spmd_loss_fn(spec: TrainSpec):
    """Returns f(params, batch) -> (loss, metrics) for use inside shard_map.

    params: global-tree with locally-sharded leaves (periods already padded
    and leading-dim sliced by stage).  batch: {"tokens": (B_loc, S) int32,
    optional "prefix": (B_loc, pre, F)}.
    """
    cfg = spec.cfg
    cfg_local = spec.cfg_local
    plan = spec.plan
    M = spec.n_micro
    ctx = make_ctx(plan)

    def fn(params, batch):
        # PERF iteration 1: mark every param varying over all mesh axes
        # *before* the pipeline loops.  Otherwise jax inserts an implicit
        # replicated->varying cast at each use site inside the tick scan,
        # whose transpose is a per-tick gradient all-reduce — hoisting
        # yields exactly one all-reduce per parameter per step (measured
        # 27.7 GiB -> ~2 GiB per device per step, phi3-mini train_4k).
        if spec.hoist_varying:
            with scopes.scope(scopes.GRAD_REDUCE):
                params = vary_all(params)
        tokens = batch["tokens"]
        B_loc = tokens.shape[0]
        S = tokens.shape[-1]
        if spec.shard_alloc is not None:
            # heterogeneous allocation: every shard is padded to B_max
            # samples per micro-batch; this shard's true count y_d selects
            # the static validity prefix (pack_batch's layout).
            mb = max(spec.shard_alloc)
            assert B_loc == M * mb, (B_loc, M, spec.shard_alloc)
            shard = (lax.axis_index("pod") * plan.data
                     + lax.axis_index("data"))
            y_here = jnp.asarray(spec.shard_alloc, jnp.int32)[shard]
            sample_valid = (jnp.arange(mb) < y_here).astype(jnp.float32)
        else:
            assert B_loc % M == 0, (B_loc, M)
            mb = B_loc // M
            sample_valid = None

        # ---- embed (vocab-parallel over tp) -----------------------------
        with scopes.scope(scopes.EMBED):
            if cfg.n_codebooks > 1:
                x = sum(vp_embed(params["embed"][cb], tokens[:, cb], ctx)
                        for cb in range(cfg.n_codebooks))
            else:
                x = vp_embed(params["embed"], tokens, ctx)
            if cfg.embed_scale:
                x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
            x = x.astype(cfg.cdtype)

        if cfg.prefix_len > 0:
            px = (batch["prefix"].astype(cfg.cdtype) @ params["prefix_proj"])
            x = jnp.concatenate([px.astype(cfg.cdtype), x], axis=1)
        S_tot = x.shape[1]
        positions = jnp.broadcast_to(jnp.arange(S_tot, dtype=jnp.int32), (mb, S_tot))

        # ---- pipeline ----------------------------------------------------
        # validity mask for zero-padded periods (identity layers): static,
        # sliced to this stage's slice of the period stack.  With a lowered
        # heterogeneous split, each stage's uniform slice holds its assigned
        # periods then padding (arrange_periods).
        n_periods = cfg.n_periods
        if spec.stage_periods is not None:
            assert len(spec.stage_periods) == plan.stage, \
                (spec.stage_periods, plan.stage)
            mask_vals = stage_period_mask(spec.stage_periods)
            k_per_stage = len(mask_vals) // plan.stage
            mask_global = jnp.asarray(mask_vals, jnp.float32)
        else:
            padded = -(-n_periods // plan.stage) * plan.stage
            k_per_stage = padded // plan.stage
            mask_global = jnp.asarray(
                [1.0] * n_periods + [0.0] * (padded - n_periods), jnp.float32)
        if plan.stage > 1:
            mask_local = lax.dynamic_slice_in_dim(
                mask_global, lax.axis_index("stage") * k_per_stage, k_per_stage)
        else:
            mask_local = mask_global

        x_micro = x.reshape(M, mb, S_tot, cfg.d_model)
        if spec.hoist_varying:
            # same hoist for the micro-batch buffer: its cotangent (the
            # embedding-gradient path) is reduced once instead of per tick
            with scopes.scope(scopes.EMBED):
                x_micro = vary_all(x_micro)
        outs, aux = pipeline_apply(params["periods"], mask_local,
                                   x_micro, positions, cfg_local, ctx,
                                   plan.stage, spec.remat,
                                   double_buffer=spec.double_buffer,
                                   compress=spec.compress,
                                   quant_tile=spec.quant_tile)

        # ---- redistribute last-stage outputs across stages ----------------
        # Every stage holds an `outs` buffer but only the last stage's is
        # real.  An all_to_all over 'stage' scatters each stage's rows so
        # device r receives row-chunk r *from every source*; taking the
        # segment that came from the last stage hands stage r exactly its
        # M/P micro-batches — the CE/head work is then stage-sharded.
        P_st = plan.stage
        stage = lax.axis_index("stage") if P_st > 1 else jnp.int32(0)
        chunk = -(-M // P_st)                      # micro-batches per stage
        start = stage * chunk
        if P_st > 1:
            with scopes.scope(scopes.REDISTRIBUTE):
                pad_rows = chunk * P_st - M
                outs_p = jnp.pad(outs, ((0, pad_rows),)
                                 + ((0, 0),) * (outs.ndim - 1)) \
                    if pad_rows else outs
                recv = lax.all_to_all(outs_p, "stage", split_axis=0,
                                      concat_axis=0, tiled=True)
                my = lax.slice_in_dim(recv, (P_st - 1) * chunk,
                                      P_st * chunk, axis=0)
        else:
            my = outs
        # ownership mask: rows past M (padding) contribute nothing
        own = (jnp.arange(chunk) + start) < M

        with scopes.scope(scopes.HEAD_CE):
            h = my.reshape(chunk * mb, S_tot, cfg.d_model)
            own_rows = jnp.repeat(own, mb)
            h = rmsnorm(params["final_norm"], h, cfg.norm_eps, cfg.zero_centered_norm)
            if cfg.prefix_len > 0:
                h_txt = h[:, cfg.prefix_len:]
            else:
                h_txt = h

            # ---- targets for this device's chunk -----------------------------
            tok_m = tokens.reshape(M, mb, *tokens.shape[1:])
            tok_my = lax.dynamic_slice_in_dim(tok_m, start, chunk, axis=0)
            tok_my = tok_my.reshape(chunk * mb, *tokens.shape[1:])

            def head_w(cb=None):
                if cfg.tie_embeddings:
                    w = params["embed"]
                    return (w[cb] if cb is not None else w).T
                w = params["head"]
                return w[cb] if cb is not None else w

            row_mask = own_rows.astype(jnp.float32)
            if sample_valid is not None:
                # rows are (micro-batch chunk, sample slot): slots past this
                # shard's y_d are padding and contribute nothing to loss, count,
                # or (through the masked CE's transpose) gradients
                row_mask = row_mask * jnp.tile(sample_valid, chunk)
            if cfg.n_codebooks > 1:
                loss_sum = jnp.zeros((), jnp.float32)
                cnt_sum = jnp.zeros((), jnp.float32)
                for cb in range(cfg.n_codebooks):
                    tgt = tok_my[:, cb, 1:]
                    msk = row_mask[:, None] * jnp.ones_like(tgt, jnp.float32)
                    l, c = vp_chunked_ce(h_txt[:, :-1], head_w(cb), tgt, msk, ctx,
                                         cfg.logit_softcap, spec.ce_chunk,
                                         v_valid=cfg.vocab_size)
                    loss_sum, cnt_sum = loss_sum + l, cnt_sum + c
            else:
                tgt = tok_my[:, 1:]
                msk = row_mask[:, None] * jnp.ones_like(tgt, jnp.float32)
                loss_sum, cnt_sum = vp_chunked_ce(h_txt[:, :-1], head_w(), tgt, msk,
                                                  ctx, cfg.logit_softcap,
                                                  spec.ce_chunk, v_valid=cfg.vocab_size)

            # ---- MTP (DeepSeek-V3) on the stage-sharded chunk ------------------
            # values are numerically tp-invariant (psum_tp'd inside) but may be
            # *marked* tp-varying by vscan; reduce over all axes and divide out
            # the tp replication so outputs are fully invariant (out_specs P()).
            red_axes = ("pod", "data", "stage", "tp")

            def allsum(x):
                return lax.psum(pcast_varying(x, red_axes), red_axes) / plan.tp

            mtp_sum = jnp.zeros((), jnp.float32)
            if cfg.mtp_depth > 0 and cfg.n_codebooks == 1 and cfg.prefix_len == 0:
                m = params["mtp"]
                emb = vp_embed(params["embed"], tok_my, ctx).astype(cfg.cdtype)
                e = jnp.concatenate([emb[:, 1:], jnp.zeros_like(emb[:, :1])], axis=1)
                zc = cfg.zero_centered_norm
                hh = jnp.concatenate([
                    rmsnorm(m["norm_e"], e, cfg.norm_eps, zc),
                    rmsnorm(m["norm_h"], h_txt, cfg.norm_eps, zc)], axis=-1)
                hh = (hh @ m["combine"]).astype(cfg.cdtype)
                pos2 = jnp.broadcast_to(jnp.arange(S_tot, dtype=jnp.int32),
                                        (hh.shape[0], S_tot))
                hh, _ = apply_period(m["block"], hh, pos2, cfg_local, ctx)
                hh = rmsnorm(m["final_norm"], hh, cfg.norm_eps, zc)
                tgt2 = jnp.concatenate([tok_my[:, 2:], jnp.zeros_like(tok_my[:, :2])],
                                       axis=1)
                msk2 = row_mask[:, None] * (jnp.arange(S_tot) < S_tot - 2)[None, :]
                l2, c2 = vp_chunked_ce(hh, head_w(), tgt2, msk2.astype(jnp.float32),
                                       ctx, cfg.logit_softcap, spec.ce_chunk,
                                       v_valid=cfg.vocab_size)
                mtp_sum = l2 / jnp.maximum(allsum(c2), 1.0)

            # ---- global reduction ---------------------------------------------

            loss_sum = allsum(loss_sum)
            cnt_sum = allsum(cnt_sum)
            # aux: sum over stages (layers), mean over dp replicas AND over the
            # M micro-batches (each tick computes a mean-style aux estimate)
            aux = allsum(aux) / (plan.dp_shards * M)
            ce = loss_sum / jnp.maximum(cnt_sum, 1.0)
            loss = ce + aux
            if cfg.mtp_depth > 0 and cfg.n_codebooks == 1 and cfg.prefix_len == 0:
                mtp = allsum(mtp_sum)
                loss = loss + MTP_WEIGHT * mtp
            else:
                mtp = jnp.zeros(())
        metrics = {"ce": ce, "aux": aux, "mtp": mtp, "tokens": cnt_sum}
        return loss, metrics

    return fn


def batch_pspecs(cfg: ModelConfig) -> dict:
    """PartitionSpecs for the training batch (inside shard_map in_specs)."""
    if cfg.n_codebooks > 1:
        specs = {"tokens": P(("pod", "data"), None, None)}
    else:
        specs = {"tokens": P(("pod", "data"), None)}
    if cfg.prefix_len > 0:
        specs["prefix"] = P(("pod", "data"), None, None)
    return specs
