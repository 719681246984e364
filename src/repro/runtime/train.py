"""Distributed train-step builder.

``build_train_step`` wires everything: global param init (periods padded to
the stage count, vocab padded to tp divisibility), PartitionSpecs, the
shard_map SPMD loss, jax.grad (DP gradient psums fall out of the shard_map
transpose), and the optimizer update (sharding-preserving elementwise).

``abstract_train_state`` builds the same thing out of ShapeDtypeStructs for
the dry-run path (no allocation).
"""

from __future__ import annotations

import dataclasses
import warnings
from functools import partial

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import scopes
from repro.distributed.mesh import MeshPlan, mesh_plan, pick_stage_count, refine_mesh
from repro.distributed.sharding import (Layout, TRAIN_LAYOUT, named,
                                        param_pspecs)
from repro.kernels.quant_transfer import roundtrip, roundtrip_ef
from repro.models.config import ModelConfig
from repro.models.model import init_model
from repro.models.module import vary_all
from repro.optim import AdamW

from .pipeline import (TrainSpec, arrange_periods, batch_pspecs, pad_periods,
                       spmd_loss_fn)


def vocab_axes(cfg: ModelConfig) -> dict:
    """Axis carrying the vocab dimension in each vocab-parallel leaf."""
    return {"embed": 0 if cfg.n_codebooks == 1 else 1,
            "head": 1 if cfg.n_codebooks == 1 else 2}


def pad_vocab_leaf(a, axis: int, cfg: ModelConfig, tp: int):
    """Zero-pad one leaf's vocab dim to a multiple of tp."""
    v = cfg.vocab_size
    v_pad = -(-v // tp) * tp - v
    if v_pad == 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, v_pad)
    return jnp.pad(a, widths)


def strip_vocab_leaf(a, axis: int, cfg: ModelConfig):
    """Inverse of ``pad_vocab_leaf``: slice back to the true vocab size."""
    sl = [slice(None)] * a.ndim
    sl[axis] = slice(0, cfg.vocab_size)
    return a[tuple(sl)]


def pad_vocab_params(params, cfg: ModelConfig, tp: int):
    """Pad embed/head vocab dims to a multiple of tp (CE masks the pad)."""
    axes = vocab_axes(cfg)
    out = dict(params)
    out["embed"] = pad_vocab_leaf(params["embed"], axes["embed"], cfg, tp)
    if "head" in params:
        out["head"] = pad_vocab_leaf(params["head"], axes["head"], cfg, tp)
    return out


def prepare_params(key, cfg: ModelConfig, plan: MeshPlan,
                   stage_periods=None):
    """Global init + structural padding for the distributed layout.

    ``stage_periods``: planner-lowered per-stage period ranges; when given,
    the period stack is arranged so each stage's uniform slice holds its
    assigned (possibly heterogeneous) period range (core.lowering).
    """
    params = init_model(key, cfg)
    if stage_periods is not None:
        params["periods"], _ = arrange_periods(params["periods"],
                                               stage_periods)
    else:
        params["periods"], _ = pad_periods(params["periods"], cfg.n_periods,
                                           plan.stage)
    params = pad_vocab_params(params, cfg, plan.tp)
    return params


def default_n_micro(cfg: ModelConfig, plan: MeshPlan, global_batch: int) -> int:
    """Micro-batch count: enough to fill the pipeline (>= 2*stages when the
    local batch allows), dividing the per-shard batch."""
    b_loc = global_batch // plan.dp_shards
    target = min(2 * plan.stage, b_loc)
    m = 1
    for cand in range(target, 0, -1):
        if b_loc % cand == 0:
            m = cand
            break
    return max(m, 1)


@dataclasses.dataclass
class TrainStep:
    spec: TrainSpec
    mesh: Mesh                      # refined mesh
    param_specs: object
    batch_specs: dict
    step_fn: object                 # jitted (params, opt_state, batch) -> ...
    loss_fn: object                 # jitted (params, batch) -> (loss, metrics)
    grad_fn: object = None          # jitted (params, batch) ->
                                    #   ((loss, metrics), grads)
    # Bounded-staleness step (spec.staleness >= 1, DESIGN.md §8): computes
    # round r's gradients but applies the *buffered* round r-1 gradients,
    # so the gradient AllReduce of round r has the whole of round r+1 to
    # overlap with.  The FIRST round has no buffer yet — drive it with
    # ``grad_fn`` alone (no optimizer update), so the update/schedule step
    # count matches the sync run exactly (sync delayed by one boundary).
    # jitted (params, opt_state, grad_buf, batch) ->
    # (params', opt_state', grads, loss, metrics).
    async_step_fn: object = None
    # jitted (params, opt_state, grad_buf) -> (params', opt_state'): apply
    # the buffered gradients synchronously (end of training / before a
    # replay migration — a failure forces a staleness barrier).
    flush_fn: object = None
    # Bucketed/compressed gradient path (spec.bucketed, DESIGN.md §10): the
    # step functions gain an error-feedback pytree argument and return its
    # successor —
    #   grad_fn(params, batch, ef) -> ((loss, metrics), grads, ef')
    #   step_fn(params, opt_state, ef, batch)
    #       -> (params', opt_state', ef', loss, metrics)
    #   async_step_fn(params, opt_state, grad_buf, ef, batch)
    #       -> (params', opt_state', grads, ef', loss, metrics)
    # ``init_ef()`` materializes the zero residual state ({} when error
    # feedback is off — the arity stays uniform); reset it whenever the
    # step is re-lowered (membership changes re-bucket the tree).
    init_ef: object = None
    # Static bucket partition [(free_axes, leaf_indices, local_sizes), ...]
    # for introspection (benchmarks / examples timeline).
    buckets: tuple = ()

    def shard_batch(self, batch_np: dict) -> dict:
        """Place a host batch on the mesh, first packing it for the spec's
        heterogeneous per-shard allocation (padding to B_max) if one is
        lowered.  The single batch-ingestion entry point — a replayed
        session's re-lowered step re-packs for the survivors' allocation
        with no change at the call site."""
        from repro.data import pack_batch, shard_batch
        with TraceAnnotation(scopes.SHARD_BATCH_SPAN):
            if self.spec.shard_alloc is not None:
                batch_np = pack_batch(batch_np, self.spec.shard_alloc,
                                      self.spec.n_micro)
            return shard_batch(batch_np, self.mesh, self.batch_specs)


def _check_shard_alloc(shard_alloc, plan: MeshPlan, n_micro: int,
                       global_batch: int, cfg: ModelConfig | None = None):
    shard_alloc = tuple(int(y) for y in shard_alloc)
    if len(shard_alloc) != plan.dp_shards:
        raise ValueError(f"shard_alloc {shard_alloc} has {len(shard_alloc)} "
                         f"entries for {plan.dp_shards} data shards")
    if min(shard_alloc) < 0 or max(shard_alloc) == 0:
        raise ValueError(f"shard_alloc {shard_alloc} must be non-negative "
                         f"with at least one positive entry")
    if n_micro * sum(shard_alloc) != global_batch:
        raise ValueError(
            f"shard_alloc {shard_alloc} allocates {sum(shard_alloc)} samples "
            f"per micro-batch; {n_micro} micro-batches do not cover the "
            f"global batch {global_batch}")
    if cfg is not None and cfg.moe is not None \
            and len(set(shard_alloc)) > 1:
        warnings.warn(
            f"heterogeneous shard_alloc {shard_alloc} with an MoE config: "
            "zero-padded sample slots still route through the experts, so "
            "they consume router capacity (displacing real tokens unless "
            "capacity_factor has headroom) and enter the aux load-balance "
            "statistics (DESIGN.md §2.1)")
    return shard_alloc


def _check_stage_periods(stage_periods, plan: MeshPlan, cfg: ModelConfig):
    stage_periods = tuple(tuple(r) for r in stage_periods)
    if len(stage_periods) != plan.stage:
        raise ValueError(f"stage_periods {stage_periods} has "
                         f"{len(stage_periods)} ranges for {plan.stage} stages")
    prev = 0
    for i, j in stage_periods:
        if i != prev or j <= i:
            raise ValueError(f"stage_periods {stage_periods} must be "
                             f"contiguous non-empty ranges from 0")
        prev = j
    if prev != cfg.n_periods:
        raise ValueError(f"stage_periods {stage_periods} covers "
                         f"[0, {prev}) but the model has "
                         f"{cfg.n_periods} periods")
    return stage_periods


def build_train_step(cfg: ModelConfig, production_mesh: Mesh,
                     global_batch: int, *, stage: int | None = None,
                     n_micro: int | None = None, optimizer: AdamW | None = None,
                     remat: bool = True, ce_chunk: int = 1024,
                     hoist_varying: bool = True, zero_opt: bool = False,
                     stage_periods=None, shard_alloc=None,
                     staleness: int = 0,
                     double_buffer: bool | None = None,
                     compress: str = "none", quant_tile: int = 256,
                     bucket_mb: float | None = None,
                     error_feedback: bool = True) -> TrainStep:
    n_heads = cfg.attn.n_heads if cfg.attn is not None else (
        cfg.d_model // cfg.rwkv.head_dim if cfg.rwkv is not None else cfg.d_model)
    model_axis = production_mesh.shape["model"]
    if stage is None:
        stage = pick_stage_count(cfg.n_layers, len(cfg.pattern), model_axis,
                                 n_heads)
    plan = mesh_plan(production_mesh, stage)
    if n_micro is None:
        if shard_alloc is not None:
            raise ValueError("shard_alloc requires an explicit n_micro")
        n_micro = default_n_micro(cfg, plan, global_batch)
    if stage_periods is not None:
        stage_periods = _check_stage_periods(stage_periods, plan, cfg)
    if shard_alloc is not None:
        shard_alloc = _check_shard_alloc(shard_alloc, plan, n_micro,
                                         global_batch, cfg)
    spec = TrainSpec(cfg=cfg, plan=plan, n_micro=n_micro, remat=remat,
                     ce_chunk=ce_chunk, hoist_varying=hoist_varying,
                     stage_periods=stage_periods, shard_alloc=shard_alloc,
                     staleness=_check_staleness(staleness),
                     double_buffer=_default_double_buffer(double_buffer,
                                                          staleness),
                     compress=_check_compress(compress),
                     quant_tile=int(quant_tile), bucket_mb=bucket_mb,
                     error_feedback=bool(error_feedback))
    return _assemble_train_step(cfg, production_mesh, spec, optimizer,
                                zero_opt)


def _check_staleness(staleness: int) -> int:
    if staleness not in (0, 1):
        raise ValueError(f"staleness must be 0 (sync) or 1 (bounded-stale "
                         f"async), got {staleness}")
    return staleness


def _check_compress(compress: str | None) -> str:
    compress = "none" if compress is None else str(compress)
    if compress not in ("none", "int8", "fp8"):
        raise ValueError(f"compress must be 'none', 'int8' or 'fp8', "
                         f"got {compress!r}")
    return compress


def _default_double_buffer(double_buffer: bool | None, staleness: int) -> bool:
    """The async runtime double-buffers by default; the sync runtime keeps
    the serialized sends (today's semantics) unless explicitly asked."""
    return staleness >= 1 if double_buffer is None else bool(double_buffer)


def train_spec_from_lowered(cfg: ModelConfig, production_mesh: Mesh, lowered,
                            *, remat: bool = True, ce_chunk: int = 1024,
                            hoist_varying: bool = True, staleness: int = 0,
                            double_buffer: bool | None = None,
                            compress: str = "none", quant_tile: int = 256,
                            bucket_mb: float | None = None,
                            error_feedback: bool = True) -> TrainSpec:
    """Derive the static step configuration from a ``core.lowering``
    ``LoweredPlan`` (duck-typed: ``stage``/``n_micro``/``stage_periods``/
    ``global_batch``/``micro_alloc`` attributes), validating mesh
    feasibility.  A heterogeneous ``micro_alloc`` is collapsed to the
    per-data-shard allocation the runtime executes
    (``core.lowering.lower_micro_alloc``); a uniform one keeps the legacy
    unpadded batch layout."""
    model_axis = production_mesh.shape["model"]
    if model_axis % lowered.stage:
        raise ValueError(f"stage count {lowered.stage} does not divide the "
                         f"mesh model axis {model_axis}")
    plan = mesh_plan(production_mesh, lowered.stage)
    dp = plan.dp_shards

    shard_alloc = None
    if getattr(lowered, "micro_alloc", None):
        from repro.core.lowering import lower_micro_alloc
        shard_alloc = lower_micro_alloc(lowered, dp)
        if len(set(shard_alloc)) == 1:
            shard_alloc = None           # uniform: no padding needed
        else:
            shard_alloc = _check_shard_alloc(shard_alloc, plan,
                                            lowered.n_micro,
                                            lowered.global_batch, cfg)
    if shard_alloc is None and (
            lowered.global_batch % dp
            or (lowered.global_batch // dp) % lowered.n_micro):
        raise ValueError(
            f"global batch {lowered.global_batch} not divisible into "
            f"{lowered.n_micro} micro-batches per {dp} data shards")
    stage_periods = _check_stage_periods(lowered.stage_periods, plan, cfg)
    return TrainSpec(cfg=cfg, plan=plan, n_micro=lowered.n_micro, remat=remat,
                     ce_chunk=ce_chunk, hoist_varying=hoist_varying,
                     stage_periods=stage_periods, shard_alloc=shard_alloc,
                     staleness=_check_staleness(staleness),
                     double_buffer=_default_double_buffer(double_buffer,
                                                          staleness),
                     compress=_check_compress(compress),
                     quant_tile=int(quant_tile), bucket_mb=bucket_mb,
                     error_feedback=bool(error_feedback))


def build_train_step_from_lowered(cfg: ModelConfig, production_mesh: Mesh,
                                  lowered, *, optimizer: AdamW | None = None,
                                  zero_opt: bool = False,
                                  **spec_kw) -> TrainStep:
    """Build (or, after a plan swap, re-build) the jitted step for a
    ``LoweredPlan`` — the session layer's entry point: params and optimizer
    state survive across calls, only the compiled step is replaced."""
    spec = train_spec_from_lowered(cfg, production_mesh, lowered, **spec_kw)
    return _assemble_train_step(cfg, production_mesh, spec, optimizer,
                                zero_opt)


# ---------------------------------------------------------------------------
# Bucketed / compressed gradient AllReduce (DESIGN.md §10)
# ---------------------------------------------------------------------------

MESH_AXES = ("pod", "data", "stage", "tp")


def _leaf_axes(spec) -> set:
    """Mesh axes appearing anywhere in a leaf's PartitionSpec."""
    used = set()
    for entry in spec:
        for ax in (entry if isinstance(entry, tuple) else (entry,)):
            if ax is not None:
                used.add(ax)
    return used


def _free_axes(spec) -> tuple:
    """Mesh axes a leaf's gradient must be psum'd over: every axis NOT
    already sharding the leaf.  A leaf sharded over an axis holds distinct
    shard values there (its gradient needs no reduction along it); a leaf
    replicated over an axis is used by every device along it (each holds a
    partial contribution).  This is exactly the reduction the shard_map
    transpose inserts for the un-bucketed path (psum is elementwise —
    reducing a concatenation equals concatenating the reductions), so
    uncompressed bucketed gradients match the legacy path to float
    reassociation (~1e-6 rel; XLA compiles a different reduction order)."""
    used = _leaf_axes(spec)
    return tuple(ax for ax in MESH_AXES if ax not in used)


def _local_size(shape, spec, mesh: Mesh) -> int:
    """Per-device element count of a leaf under its PartitionSpec."""
    n = 1
    for d, dim in enumerate(shape):
        div = 1
        if d < len(spec) and spec[d] is not None:
            entry = spec[d]
            for ax in (entry if isinstance(entry, tuple) else (entry,)):
                div *= mesh.shape[ax]
        n *= dim // div
    return n


def grad_buckets(abstract_params, pspecs, mesh: Mesh,
                 bucket_mb: float | None):
    """Static bucket partition of the gradient pytree.

    Leaves are grouped by free-axes set (one psum serves a whole bucket)
    and greedily packed into ``bucket_mb``-bounded buckets in tree-flatten
    order.  Each bucket's psum depends only on its own leaves' cotangents,
    so XLA's latency-hiding scheduler can launch early buckets' AllReduces
    while later layers are still in backward (DDP-style partial syncs —
    ``plan_dp(overlap=True)``'s pricing, now on the HPP gradient stream).

    Returns ``[(free_axes, leaf_indices, local_sizes), ...]``; leaf indices
    refer to ``jax.tree_util.tree_leaves`` order of the param tree.
    """
    leaves, _ = jax.tree_util.tree_flatten(abstract_params)
    spec_leaves = jax.tree_util.tree_leaves(
        jax.tree.map(lambda _, s: s, abstract_params, pspecs),
        is_leaf=lambda x: isinstance(x, P))
    assert len(leaves) == len(spec_leaves), (len(leaves), len(spec_leaves))
    cap = float("inf") if bucket_mb is None else float(bucket_mb) * (1 << 20)
    groups: dict = {}
    for i, (leaf, sp) in enumerate(zip(leaves, spec_leaves)):
        groups.setdefault(_free_axes(sp), []).append(
            (i, _local_size(leaf.shape, sp, mesh)))
    buckets = []
    for free, entries in sorted(groups.items()):
        cur: list = []
        cur_bytes = 0.0
        for i, n in entries:
            if cur and cur_bytes + n * 4 > cap:
                buckets.append((free, tuple(j for j, _ in cur),
                                tuple(m for _, m in cur)))
                cur, cur_bytes = [], 0.0
            cur.append((i, n))
            cur_bytes += n * 4
        if cur:
            buckets.append((free, tuple(j for j, _ in cur),
                            tuple(m for _, m in cur)))
    return buckets


def _ef_key(bi: int) -> str:
    return f"bucket{bi}"


def ef_specs_for(buckets):
    """PartitionSpecs for the error-feedback pytree: one per-device flat
    residual per bucket, stacked over every mesh axis on dim 0 (global
    shape ``(n_devices, L_b)``, local ``(1, L_b)``)."""
    return {_ef_key(bi): P(MESH_AXES, None) for bi in range(len(buckets))}


def ef_zeros(buckets, mesh: Mesh, shardings):
    """Materialize the zero error-feedback state on the mesh."""
    n_dev = 1
    for ax in MESH_AXES:
        n_dev *= mesh.shape[ax]
    out = {}
    for bi, (_, _, sizes) in enumerate(buckets):
        k = _ef_key(bi)
        out[k] = jax.device_put(jnp.zeros((n_dev, sum(sizes)), jnp.float32),
                                shardings[k])
    return out


def _bucketed_grad_fn(spec: TrainSpec, base_loss, buckets):
    """Inside-shard_map gradient with explicit per-bucket psums.

    ``jax.value_and_grad`` of the SPMD loss *inside* the shard_map body
    yields each device's unreduced local contribution (boundary casts are
    identity on this side of the shard_map boundary); every bucket is then
    flattened, optionally quantized (with the error-feedback residual
    carried across steps), and psum'd over its free axes.  The quantization
    compresses exactly the bytes each device contributes to the AllReduce.
    """
    fmt, tile, ef_on = spec.compress, spec.quant_tile, spec.error_feedback

    def fn(params, batch, ef):
        # Differentiate w.r.t. params already cast varying over every axis:
        # the transpose of that cast is the psum over each leaf's free axes,
        # so taking the gradient before it leaves each device's unreduced
        # local contribution, which the per-bucket psums below then reduce.
        (loss, metrics), grads = jax.value_and_grad(
            base_loss, has_aux=True)(vary_all(params), batch)
        leaves, treedef = jax.tree_util.tree_flatten(grads)
        new_leaves = list(leaves)
        new_ef = dict(ef)
        with scopes.scope(scopes.GRAD_REDUCE):
            for bi, (free, idxs, _sizes) in enumerate(buckets):
                flat = jnp.concatenate(
                    [leaves[i].astype(jnp.float32).reshape(-1) for i in idxs])
                if fmt != "none":
                    if ef_on:
                        k = _ef_key(bi)
                        flat, res = roundtrip_ef(flat, ef[k][0], fmt=fmt,
                                                 tile=tile)
                        new_ef[k] = res[None]
                    else:
                        flat = roundtrip(flat, fmt=fmt, tile=tile)
                if free:
                    flat = jax.lax.psum(flat, free)
                off = 0
                for i in idxs:
                    n = new_leaves[i].size
                    new_leaves[i] = flat[off:off + n].reshape(
                        leaves[i].shape).astype(leaves[i].dtype)
                    off += n
        return loss, metrics, jax.tree_util.tree_unflatten(
            treedef, new_leaves), new_ef

    return fn


def _update(optimizer, grads, opt_state, params):
    """The optimizer update, under its profiler scope."""
    with scopes.scope(scopes.OPTIMIZER):
        return optimizer.update(grads, opt_state, params)


def _assemble_train_step(cfg: ModelConfig, production_mesh: Mesh,
                         spec: TrainSpec, optimizer: AdamW | None,
                         zero_opt: bool) -> TrainStep:
    plan = spec.plan
    stage_periods = spec.stage_periods
    mesh = refine_mesh(production_mesh, plan.stage)
    optimizer = optimizer or AdamW(lr=1e-3)

    # --- specs (built against an abstract param tree) ----------------------
    abstract = jax.eval_shape(
        lambda k: prepare_params(k, cfg, plan, stage_periods),
        jax.random.PRNGKey(0))
    kv_repl = cfg.attn is not None and cfg.attn.n_kv_heads % plan.tp != 0
    layout = dataclasses.replace(TRAIN_LAYOUT, kv_replicated=kv_repl)
    pspecs = param_pspecs(abstract, layout)
    bspecs = batch_pspecs(cfg)

    spmd = spmd_loss_fn(spec)
    metrics_sp = {"ce": P(), "aux": P(), "mtp": P(), "tokens": P()}
    sharded_loss = jax.shard_map(spmd, mesh=mesh,
                             in_specs=(pspecs, bspecs),
                             out_specs=(P(), metrics_sp))

    def loss_fn(params, batch):
        return sharded_loss(params, batch)

    param_shardings = named(mesh, pspecs)
    batch_sh = named(mesh, bspecs)
    jit_loss = jax.jit(loss_fn, in_shardings=(param_shardings, batch_sh))
    opt_sh = _opt_shardings(optimizer, abstract, param_shardings,
                            zero_sharding=zero_opt)

    if spec.bucketed:
        return _assemble_bucketed(spec, mesh, optimizer, abstract, pspecs,
                                  bspecs, spmd, metrics_sp, param_shardings,
                                  batch_sh, opt_sh, jit_loss)

    def grad_fn(params, batch):
        return jax.value_and_grad(loss_fn, has_aux=True)(params, batch)

    def step_fn(params, opt_state, batch):
        (loss, metrics), grads = grad_fn(params, batch)
        new_params, new_opt = _update(optimizer, grads, opt_state, params)
        return new_params, new_opt, loss, metrics

    jit_grad = jax.jit(grad_fn, in_shardings=(param_shardings, batch_sh))
    jit_step = jax.jit(step_fn, in_shardings=(
        param_shardings, opt_sh, batch_sh),
        out_shardings=(param_shardings, opt_sh, None, None))

    jit_async = jit_flush = None
    if spec.staleness >= 1:
        # Bounded-staleness step: the update consumes the PREVIOUS round's
        # gradient buffer, so nothing downstream of this round's gradient
        # AllReduce is on this round's critical path — the AllReduce of
        # round r may complete any time before the r+1 boundary update
        # (staleness 1; DESIGN.md §8).  Gradients share the param tree
        # structure and shardings (the shard_map transpose psums them onto
        # the param specs).
        def async_step_fn(params, opt_state, grad_buf, batch):
            (loss, metrics), grads = grad_fn(params, batch)
            new_params, new_opt = _update(optimizer, grad_buf, opt_state, params)
            return new_params, new_opt, grads, loss, metrics

        def flush_fn(params, opt_state, grad_buf):
            return _update(optimizer, grad_buf, opt_state, params)

        jit_async = jax.jit(async_step_fn, in_shardings=(
            param_shardings, opt_sh, param_shardings, batch_sh),
            out_shardings=(param_shardings, opt_sh, param_shardings,
                           None, None))
        jit_flush = jax.jit(flush_fn, in_shardings=(
            param_shardings, opt_sh, param_shardings),
            out_shardings=(param_shardings, opt_sh))

    return TrainStep(spec=spec, mesh=mesh, param_specs=pspecs,
                     batch_specs=bspecs, step_fn=jit_step, loss_fn=jit_loss,
                     grad_fn=jit_grad, async_step_fn=jit_async,
                     flush_fn=jit_flush)


def _assemble_bucketed(spec: TrainSpec, mesh: Mesh, optimizer, abstract,
                       pspecs, bspecs, spmd, metrics_sp, param_shardings,
                       batch_sh, opt_sh, jit_loss) -> TrainStep:
    """Step assembly for the bucketed/compressed gradient path.

    The gradient is taken INSIDE the shard_map body and reduced by explicit
    per-bucket psums over each leaf's free axes — semantically the same
    reduction the legacy outside-grad transpose inserts, but addressable:
    each bucket is a separate, data-independent AllReduce that XLA can
    launch as soon as its leaves' backward completes, and the compressed
    variant quantizes exactly the per-device contribution that crosses the
    wire (error-feedback residual carried in the ``ef`` pytree).
    """
    buckets = tuple(grad_buckets(abstract, pspecs, mesh, spec.bucket_mb))
    use_ef = spec.compress != "none" and spec.error_feedback
    ef_sp = ef_specs_for(buckets) if use_ef else {}
    ef_sh = named(mesh, ef_sp)

    sharded_grad = jax.shard_map(_bucketed_grad_fn(spec, spmd, buckets),
                             mesh=mesh,
                             in_specs=(pspecs, bspecs, ef_sp),
                             out_specs=(P(), metrics_sp, pspecs, ef_sp))

    def grad_fn(params, batch, ef):
        loss, metrics, grads, ef = sharded_grad(params, batch, ef)
        return (loss, metrics), grads, ef

    def step_fn(params, opt_state, ef, batch):
        (loss, metrics), grads, ef = grad_fn(params, batch, ef)
        new_params, new_opt = _update(optimizer, grads, opt_state, params)
        return new_params, new_opt, ef, loss, metrics

    jit_grad = jax.jit(grad_fn, in_shardings=(param_shardings, batch_sh,
                                              ef_sh))
    jit_step = jax.jit(step_fn, in_shardings=(
        param_shardings, opt_sh, ef_sh, batch_sh),
        out_shardings=(param_shardings, opt_sh, ef_sh, None, None))

    jit_async = jit_flush = None
    if spec.staleness >= 1:
        def async_step_fn(params, opt_state, grad_buf, ef, batch):
            (loss, metrics), grads, ef = grad_fn(params, batch, ef)
            new_params, new_opt = _update(optimizer, grad_buf, opt_state, params)
            return new_params, new_opt, grads, ef, loss, metrics

        def flush_fn(params, opt_state, grad_buf):
            return _update(optimizer, grad_buf, opt_state, params)

        jit_async = jax.jit(async_step_fn, in_shardings=(
            param_shardings, opt_sh, param_shardings, ef_sh, batch_sh),
            out_shardings=(param_shardings, opt_sh, param_shardings, ef_sh,
                           None, None))
        jit_flush = jax.jit(flush_fn, in_shardings=(
            param_shardings, opt_sh, param_shardings),
            out_shardings=(param_shardings, opt_sh))

    def init_ef():
        return ef_zeros(buckets, mesh, ef_sh) if use_ef else {}

    return TrainStep(spec=spec, mesh=mesh, param_specs=pspecs,
                     batch_specs=bspecs, step_fn=jit_step, loss_fn=jit_loss,
                     grad_fn=jit_grad, async_step_fn=jit_async,
                     flush_fn=jit_flush, init_ef=init_ef, buckets=buckets)


def _zero_moment_shardings(abstract_params, param_shardings):
    """ZeRO-1-style: shard each moment over ('pod','data') on the first dim
    that is unsharded and divisible — fp32 Adam moments dominate the training
    footprint, and they are only touched in the (resharded) update step."""
    mesh = jax.tree.leaves(param_shardings)[0].mesh
    dp = mesh.shape["pod"] * mesh.shape["data"]

    def shard_one(leaf, named_sh):
        spec = list(named_sh.spec) + [None] * (leaf.ndim - len(named_sh.spec))
        used = set()
        for entry in spec:
            for ax in (entry if isinstance(entry, tuple) else (entry,)):
                used.add(ax)
        # only dp axes this tensor doesn't already use (e.g. EP'd experts
        # are already sharded over 'data' — they are "ZeRO'd" by EP)
        free = tuple(ax for ax in ("pod", "data") if ax not in used)
        n = 1
        for ax in free:
            n *= mesh.shape[ax]
        if n <= 1:
            return named_sh
        for i, dim in enumerate(leaf.shape):
            if spec[i] is None and dim % n == 0 and dim >= n:
                spec[i] = free if len(free) > 1 else free[0]
                return NamedSharding(mesh, P(*spec))
        return named_sh           # too small / indivisible: keep param layout

    return jax.tree.map(shard_one, abstract_params, param_shardings)


def _opt_shardings(optimizer, abstract_params, param_shardings,
                   zero_sharding: bool = False):
    """Moments share the param shardings (or a ZeRO-1 dp-sharded variant);
    the step counter is replicated."""
    from repro.optim import AdamWState, SGDState
    mesh = jax.tree.leaves(param_shardings)[0].mesh
    rep = NamedSharding(mesh, P())
    moments = (_zero_moment_shardings(abstract_params, param_shardings)
               if zero_sharding else param_shardings)
    st = jax.eval_shape(optimizer.init, abstract_params)
    if isinstance(st, AdamWState):
        return AdamWState(rep, moments, moments)
    if isinstance(st, SGDState):
        return SGDState(rep, moments)
    raise TypeError(type(st))


def init_train_state(key, ts: TrainStep, optimizer: AdamW | None = None):
    """Materialize sharded params + optimizer state on the mesh."""
    optimizer = optimizer or AdamW(lr=1e-3)
    cfg, plan = ts.spec.cfg, ts.spec.plan
    shardings = named(ts.mesh, ts.param_specs)
    params = jax.jit(lambda k: prepare_params(k, cfg, plan,
                                              ts.spec.stage_periods),
                     out_shardings=shardings)(key)
    opt_state = jax.jit(optimizer.init,
                        out_shardings=_opt_shardings(
                            optimizer, jax.eval_shape(lambda: params),
                            shardings))(params)
    return params, opt_state
