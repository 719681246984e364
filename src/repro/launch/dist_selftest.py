"""Distributed-runtime self-test: HPP train parity vs single-device reference.

Runs every architecture family's smoke config through the full shard_map
pipeline (data=2, stage=2, tp=2 on 8 host devices) and compares the loss to
the single-device ``repro.models.model.loss_fn`` with identical params.

Invoked by tests/test_distributed.py in a subprocess (so the host-device
flag does not leak into other tests) and usable directly:

    PYTHONPATH=src python -m repro.launch.dist_selftest [arch ...]
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import dataclasses  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

DEFAULT_ARCHS = [
    "phi3-mini-3.8b",          # dense MHA
    "gemma-2b",                # MQA kv=1 (replicated-KV slice path), GeGLU, tied
    "gemma2-2b",               # sliding window + softcaps + post norms
    "phi3.5-moe-42b-a6.6b",    # MoE with EP all_to_all
    "jamba-1.5-large-398b",    # hybrid mamba + attn + MoE
    "rwkv6-7b",                # attention-free
    "musicgen-large",          # multi-codebook + prefix
    "internvl2-2b",            # VLM prefix
    "deepseek-v3-671b",        # MLA + sigmoid router + MTP
]

TOL = 2e-3


def run_arch(arch: str, devices) -> float:
    from repro.configs import get_smoke_config
    from repro.data import SyntheticLM
    from repro.models.frontend import frontend_dim
    from repro.models.model import init_model, loss_fn as local_loss_fn
    from repro.runtime.train import build_train_step, init_train_state

    cfg = get_smoke_config(arch)
    if cfg.moe is not None:
        # capacity drops are the one legitimate local/global divergence —
        # disable them for the parity check
        cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, capacity_factor=64.0))
    B, S = 8, 64
    mesh_prod = Mesh(np.array(devices).reshape(2, 4), ("data", "model"))
    ts = build_train_step(cfg, mesh_prod, global_batch=B, stage=2, n_micro=4)

    key = jax.random.PRNGKey(0)
    ds = SyntheticLM(cfg.vocab_size, S, n_codebooks=cfg.n_codebooks,
                     prefix_len=cfg.prefix_len, prefix_dim=frontend_dim(cfg))
    batch_np = ds.batch(0, B)
    batch = ts.shard_batch(batch_np)
    params, opt_state = init_train_state(key, ts)

    loss_d, metrics = ts.loss_fn(params, batch)

    ref_params = init_model(key, cfg)
    loss_r, metrics_r = jax.jit(lambda p, b: local_loss_fn(p, b, cfg, ce_chunk=1024))(
        ref_params, {k: jnp.asarray(v) for k, v in batch_np.items()})

    # CE must match exactly; the MoE aux loss is a per-shard/per-microbatch
    # estimate (as in production systems), so the total gets a looser bound
    diff = abs(float(metrics["ce"]) - float(metrics_r["ce"]))
    diff_total = abs(float(loss_d) - float(loss_r))
    assert diff_total < 0.05, (arch, diff_total)

    # and one optimizer step must reduce the loss
    new_params, new_opt, l0, _ = ts.step_fn(params, opt_state, batch)
    l1, _ = ts.loss_fn(new_params, batch)
    improved = float(l1) < float(l0)
    print(f"{arch:26s} dist={float(loss_d):.5f} ref={float(loss_r):.5f} "
          f"diff={diff:.2e} step {float(l0):.4f}->{float(l1):.4f} "
          f"{'OK' if diff < TOL and improved else 'FAIL'}", flush=True)
    if diff >= TOL or not improved:
        raise SystemExit(f"{arch}: parity diff {diff} (tol {TOL}) improved={improved}")
    return diff


def run_arch_hetero(arch: str, devices) -> float:
    """Heterogeneous intra-stage allocation (Algorithm 1) on the real
    runtime: a y=(3,1) sample split across the 2-wide data axis, padded to
    B_max=3 with static validity masks.  Asserts loss parity vs the
    single-device reference, *gradient* parity vs the uniform-allocation
    baseline on the same global batch (dense models; MoE aux statistics are
    per-shard estimates, so only CE is compared there), bit-identical param
    shapes, and a loss-reducing optimizer step through the padded pipeline."""
    from repro.configs import get_smoke_config
    from repro.data import SyntheticLM
    from repro.models.frontend import frontend_dim
    from repro.models.model import init_model, loss_fn as local_loss_fn
    from repro.runtime.train import build_train_step, init_train_state

    cfg = get_smoke_config(arch)
    if cfg.moe is not None:
        cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, capacity_factor=64.0))
    cfg = cfg.replace(n_layers=4 * len(cfg.pattern))       # 4 periods
    B, S, M = 16, 64, 4
    mesh_prod = Mesh(np.array(devices).reshape(2, 4), ("data", "model"))
    ts_u = build_train_step(cfg, mesh_prod, global_batch=B, stage=2, n_micro=M)
    ts_h = build_train_step(cfg, mesh_prod, global_batch=B, stage=2, n_micro=M,
                            shard_alloc=(3, 1))
    assert ts_h.spec.shard_alloc == (3, 1)

    key = jax.random.PRNGKey(0)
    ds = SyntheticLM(cfg.vocab_size, S, n_codebooks=cfg.n_codebooks,
                     prefix_len=cfg.prefix_len, prefix_dim=frontend_dim(cfg))
    batch_np = ds.batch(0, B)
    batch_u = ts_u.shard_batch(batch_np)
    batch_h = ts_h.shard_batch(batch_np)
    params_u, opt_u = init_train_state(key, ts_u)
    params_h, opt_h = init_train_state(key, ts_h)

    ref_params = init_model(key, cfg)
    _, metrics_r = jax.jit(lambda p, b: local_loss_fn(p, b, cfg, ce_chunk=1024))(
        ref_params, {k: jnp.asarray(v) for k, v in batch_np.items()})
    (_, metrics_u), grads_u = ts_u.grad_fn(params_u, batch_u)
    (_, metrics_h), grads_h = ts_h.grad_fn(params_h, batch_h)
    diff_ref = abs(float(metrics_h["ce"]) - float(metrics_r["ce"]))
    diff_u = abs(float(metrics_h["ce"]) - float(metrics_u["ce"]))
    assert float(metrics_h["tokens"]) == float(metrics_u["tokens"])

    # gradient parity: same global batch, unbalanced vs uniform allocation
    worst_grad = 0.0
    for gu, gh in zip(jax.tree.leaves(grads_u), jax.tree.leaves(grads_h)):
        assert gu.shape == gh.shape and gu.dtype == gh.dtype
        if cfg.moe is None:
            d = float(jnp.max(jnp.abs(gu - gh)))
            scale = max(float(jnp.max(jnp.abs(gu))), 1e-12)
            worst_grad = max(worst_grad, d / scale)

    new_h, _, l0, _ = ts_h.step_fn(params_h, opt_h, batch_h)
    l1, _ = ts_h.loss_fn(new_h, batch_h)
    improved = float(l1) < float(l0)

    # the same unbalanced allocation as a full planner Plan, lowered through
    # plan_to_train_step (check_against_simulator validates the Eq. 8
    # allocation-scaled per-device times before anything compiles)
    ts_p = _hetero_plan_step(cfg, mesh_prod, micro_batch=B // M, n_micro=M)
    assert ts_p.spec.shard_alloc == (3, 1), ts_p.spec.shard_alloc
    params_p, _ = init_train_state(key, ts_p)
    _, metrics_p = ts_p.loss_fn(params_p, ts_p.shard_batch(batch_np))
    diff_p = abs(float(metrics_p["ce"]) - float(metrics_r["ce"]))

    ok = (diff_ref < TOL and diff_u < TOL and diff_p < TOL
          and worst_grad < 1e-4 and improved)
    print(f"{arch:26s} [hetero] y={ts_h.spec.shard_alloc} ref diff="
          f"{diff_ref:.2e} uniform diff={diff_u:.2e} plan diff={diff_p:.2e} "
          f"grad rel={worst_grad:.2e} step {float(l0):.4f}->{float(l1):.4f} "
          f"{'OK' if ok else 'FAIL'}", flush=True)
    if not ok:
        raise SystemExit(f"{arch}: hetero allocation parity ref={diff_ref} "
                         f"uniform={diff_u} plan={diff_p} grad={worst_grad} "
                         f"improved={improved}")
    return max(diff_ref, diff_u)


def _hetero_plan_step(cfg, mesh_prod, micro_batch: int, n_micro: int):
    """A 2-stage Plan whose every stage allocates y=(3,1) across its
    two-device group (a TX2 paired with a nano), lowered end-to-end."""
    from repro.core.costmodel import Step, allreduce_time, kp_policy, \
        round_latency
    from repro.core.hardware import JETSON_NANO, JETSON_TX2, Cluster
    from repro.core.lowering import plan_to_train_step
    from repro.core.planner import Plan, StagePlan, _comm_step
    from repro.core.profiler import LayerTable, Profile

    table = LayerTable.from_model_config(cfg, 64)
    cluster = Cluster((JETSON_TX2, JETSON_NANO, JETSON_TX2, JETSON_NANO))
    prof = Profile.analytic(table, cluster, max_batch=micro_batch * n_micro)
    cut = 1 + (table.L - 2) // 2                           # period boundary
    y = (3, 1)
    assert sum(y) == micro_batch, (y, micro_batch)
    stages, steps = [], []
    for p, (i, j, group) in enumerate([(0, cut, (0, 1)), (cut, table.L, (2, 3))]):
        ef = max(prof.t_fwd(d, yy, i, j) for d, yy in zip(group, y))
        eb = max(prof.t_bwd(d, yy, i, j) for d, yy in zip(group, y))
        ta = allreduce_time(table.param_bytes(i, j), group, prof.cluster)
        steps.append(Step("exec", ef, eb, ta, group, (i, j), y))
        stages.append(StagePlan((i, j), group, y, kp_policy(2, p)))
        if p == 0:
            steps.append(_comm_step(prof, micro_batch, cut, (0, 1), (2, 3)))
    plan = Plan(cfg.name, tuple(stages), tuple(steps), micro_batch, n_micro,
                round_latency(tuple(steps), n_micro), "hand")
    ts, _ = plan_to_train_step(plan, prof, cfg, mesh_prod)
    return ts


def run_async(arch: str, devices) -> float:
    """Async 1F1B runtime equivalence (DESIGN.md §8).

    1. staleness 0 + double-buffered sends is gradient-BIT-IDENTICAL to the
       synchronous runtime on the same batch (the overlap only moves the
       tick a transfer occupies, never the per-micro-batch math);
    2. a staleness-1 run applies round r's gradients at the r+1 boundary:
       after N steps + a final flush its loss lands within tolerance of the
       sync run on the same batch stream (bounded-staleness convergence),
       and both arms applied exactly the same number of optimizer updates
       (the first async round computes gradients only — no update, no
       schedule-step skew)."""
    from repro.configs import get_smoke_config
    from repro.data import SyntheticLM
    from repro.models.frontend import frontend_dim
    from repro.runtime.train import build_train_step, init_train_state

    cfg = get_smoke_config(arch)
    if cfg.moe is not None:
        cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, capacity_factor=64.0))
    B, S, M, N = 8, 64, 4, 8
    mesh_prod = Mesh(np.array(devices).reshape(2, 4), ("data", "model"))
    ts_sync = build_train_step(cfg, mesh_prod, global_batch=B, stage=2,
                               n_micro=M)
    ts_db = build_train_step(cfg, mesh_prod, global_batch=B, stage=2,
                             n_micro=M, staleness=0, double_buffer=True)
    ts_async = build_train_step(cfg, mesh_prod, global_batch=B, stage=2,
                                n_micro=M, staleness=1)
    assert ts_async.spec.double_buffer, "staleness 1 defaults to overlap"

    key = jax.random.PRNGKey(0)
    ds = SyntheticLM(cfg.vocab_size, S, n_codebooks=cfg.n_codebooks,
                     prefix_len=cfg.prefix_len, prefix_dim=frontend_dim(cfg))
    batch_np = ds.batch(0, B)

    # 1) bit-identical gradients: sync vs double-buffered staleness-0
    params, opt0 = init_train_state(key, ts_sync)
    (_, m_sync), g_sync = ts_sync.grad_fn(params, ts_sync.shard_batch(batch_np))
    (_, m_db), g_db = ts_db.grad_fn(params, ts_db.shard_batch(batch_np))
    n_diff = sum(0 if bool(jnp.array_equal(a, b)) else 1
                 for a, b in zip(jax.tree.leaves(g_sync),
                                 jax.tree.leaves(g_db)))
    bit_identical = n_diff == 0 and float(m_sync["ce"]) == float(m_db["ce"])

    # 2) staleness-1 convergence smoke vs sync on the SAME batch stream:
    # the first async round computes gradients only (no optimizer update),
    # every later round applies the previous round's buffer, the flush
    # applies the final round — so both arms apply exactly N+1 updates
    p_a, o_a = init_train_state(key, ts_async)
    (l0a, _), buf = ts_async.grad_fn(p_a, ts_async.shard_batch(batch_np))
    grads_live = any(float(jnp.max(jnp.abs(x))) > 0
                     for x in jax.tree.leaves(buf))
    p_s, o_s, _, _ = ts_sync.step_fn(params, opt0,
                                     ts_sync.shard_batch(batch_np))
    for step in range(N):
        b_np = ds.batch(step + 1, B)
        p_s, o_s, _, _ = ts_sync.step_fn(p_s, o_s,
                                         ts_sync.shard_batch(b_np))
        p_a, o_a, buf, _, _ = ts_async.async_step_fn(
            p_a, o_a, buf, ts_async.shard_batch(b_np))
    p_a, o_a = ts_async.flush_fn(p_a, o_a, buf)
    steps_match = int(o_a.step) == int(o_s.step)
    l_s, _ = ts_sync.loss_fn(p_s, ts_sync.shard_batch(batch_np))
    l_a, _ = ts_async.loss_fn(p_a, ts_async.shard_batch(batch_np))
    gap = abs(float(l_s) - float(l_a))
    converged = gap < 0.15 and float(l_a) < float(l0a)

    ok = bit_identical and grads_live and steps_match and converged
    print(f"{arch:26s} [async] grad-bit-identical={bit_identical} "
          f"(diff leaves {n_diff}) updates-match={steps_match} "
          f"stale-vs-sync loss gap={gap:.4f} "
          f"({float(l_s):.4f} vs {float(l_a):.4f}) "
          f"{'OK' if ok else 'FAIL'}", flush=True)
    if not ok:
        raise SystemExit(f"{arch}: async equivalence bit={bit_identical} "
                         f"updates={steps_match} live={grads_live} "
                         f"gap={gap}")
    return gap


INT8_TOL = 5e-2           # pinned compressed-vs-raw gradient rel-err bound


def run_compress(arch: str, devices) -> float:
    """Compressed boundary transfers + bucketed gradient AllReduce
    (DESIGN.md §10) on the real runtime, staleness 0:

    1. the *bucketed but uncompressed* gradient path matches the legacy
       per-leaf psum path to float reassociation (~1e-5 rel — same math,
       different reduction order);
    2. int8-compressed gradients (quantized boundary activations AND the
       quantized bucketed AllReduce) land within the pinned ``INT8_TOL``
       of the uncompressed gradients on the same params/batch, with live
       error-feedback residuals;
    3. error feedback is unbiased in the telescoping-sum sense: the mean
       of T compressed gradient rounds on a frozen params/batch drifts
       toward the raw gradient, beating the no-feedback quantizer.  This
       arm runs one stage, so the AllReduce, which feedback covers, is the
       only compressed transfer;
    4. one compressed optimizer step reduces the loss.
    """
    from repro.configs import get_smoke_config
    from repro.data import SyntheticLM
    from repro.models.frontend import frontend_dim
    from repro.runtime.train import build_train_step, init_train_state

    cfg = get_smoke_config(arch)
    if cfg.moe is not None:
        cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, capacity_factor=64.0))
    B, S, M, T = 8, 64, 4, 6
    mesh_prod = Mesh(np.array(devices).reshape(2, 4), ("data", "model"))
    ts_base = build_train_step(cfg, mesh_prod, global_batch=B, stage=2,
                               n_micro=M)
    ts_bkt = build_train_step(cfg, mesh_prod, global_batch=B, stage=2,
                              n_micro=M, bucket_mb=4.0)
    ts_q = build_train_step(cfg, mesh_prod, global_batch=B, stage=2,
                            n_micro=M, compress="int8")
    # step 3: one stage, so no boundary hops are compressed
    ts_raw1 = build_train_step(cfg, mesh_prod, global_batch=B, stage=1,
                               n_micro=M)
    ts_q1 = build_train_step(cfg, mesh_prod, global_batch=B, stage=1,
                             n_micro=M, compress="int8")
    ts_qnef1 = build_train_step(cfg, mesh_prod, global_batch=B, stage=1,
                                n_micro=M, compress="int8",
                                error_feedback=False)
    assert ts_bkt.spec.bucketed and ts_q.spec.bucketed

    key = jax.random.PRNGKey(0)
    ds = SyntheticLM(cfg.vocab_size, S, n_codebooks=cfg.n_codebooks,
                     prefix_len=cfg.prefix_len, prefix_dim=frontend_dim(cfg))
    batch_np = ds.batch(0, B)
    params, opt0 = init_train_state(key, ts_base)

    def rel(ga, gb):
        worst = 0.0
        for a, b in zip(jax.tree.leaves(ga), jax.tree.leaves(gb)):
            assert a.shape == b.shape and a.dtype == b.dtype
            d = float(jnp.max(jnp.abs(a - b)))
            scale = max(float(jnp.max(jnp.abs(b))), 1e-12)
            worst = max(worst, d / scale)
        return worst

    (_, m0), g0 = ts_base.grad_fn(params, ts_base.shard_batch(batch_np))

    # 1) bucketed-uncompressed == legacy up to reduction-order reassociation
    (_, mb), gb, _ = ts_bkt.grad_fn(params, ts_bkt.shard_batch(batch_np),
                                    ts_bkt.init_ef())
    worst_bkt = rel(gb, g0)

    # 2) int8 end-to-end (compressed ppermute + compressed bucketed psum)
    batch_q = ts_q.shard_batch(batch_np)
    (_, mq), gq, ef = ts_q.grad_fn(params, batch_q, ts_q.init_ef())
    worst_q = rel(gq, g0)
    ef_live = any(float(jnp.max(jnp.abs(x))) > 0 for x in jax.tree.leaves(ef))
    ce_gap = abs(float(mq["ce"]) - float(m0["ce"]))

    # 3) telescoping error feedback: mean of T rounds on frozen params/batch
    #    approaches the raw gradient; without feedback the quantizer bias is
    #    whatever round 1 produced, every round
    params1, _ = init_train_state(key, ts_raw1)
    (_, _), g1 = ts_raw1.grad_fn(params1, ts_raw1.shard_batch(batch_np))
    batch_q1 = ts_q1.shard_batch(batch_np)
    acc = jax.tree.map(jnp.zeros_like, g1)
    ef_t = ts_q1.init_ef()
    for _ in range(T):
        (_, _), g_t, ef_t = ts_q1.grad_fn(params1, batch_q1, ef_t)
        acc = jax.tree.map(jnp.add, acc, g_t)
    mean_ef = jax.tree.map(lambda x: x / T, acc)
    bias_ef = rel(mean_ef, g1)
    (_, _), g_nef, _ = ts_qnef1.grad_fn(params1,
                                        ts_qnef1.shard_batch(batch_np),
                                        ts_qnef1.init_ef())
    bias_nef = rel(g_nef, g1)
    ef_wins = bias_ef < bias_nef

    # 4) one compressed step reduces the loss (step_fn's bucketed arity)
    p1, _, ef1, l0, _ = ts_q.step_fn(params, opt0, ts_q.init_ef(), batch_q)
    l1, _ = ts_q.loss_fn(p1, batch_q)
    improved = float(l1) < float(l0)

    ok = (worst_bkt < 1e-4 and worst_q < INT8_TOL and ef_live and ce_gap < 0.02
          and ef_wins and improved)
    print(f"{arch:26s} [compress] bucketed rel={worst_bkt:.2e} int8 "
          f"rel={worst_q:.2e} ce gap={ce_gap:.2e} ef-bias {bias_nef:.2e}->"
          f"{bias_ef:.2e} step {float(l0):.4f}->{float(l1):.4f} "
          f"{'OK' if ok else 'FAIL'}", flush=True)
    if not ok:
        raise SystemExit(f"{arch}: compressed parity bucketed={worst_bkt} "
                         f"int8={worst_q} ce={ce_gap} ef_live={ef_live} "
                         f"ef {bias_nef}->{bias_ef} improved={improved}")
    return worst_q


def run_arch_planned(arch: str, devices) -> float:
    """Full planner->lowering->runtime path: profile an edge cluster, run
    Algorithm 2 restricted to mesh-feasible stage counts, lower the plan
    (heterogeneous period split + n_micro + K_p cross-check against the
    simulator), and verify train-loss parity vs the single-device model."""
    from repro.configs import get_smoke_config
    from repro.core.hardware import env_d
    from repro.core.lowering import plan_to_train_step
    from repro.core.planner import plan_hpp
    from repro.core.profiler import LayerTable, Profile
    from repro.data import SyntheticLM
    from repro.models.frontend import frontend_dim
    from repro.models.model import init_model, loss_fn as local_loss_fn
    from repro.runtime.train import build_train_step, init_train_state

    cfg = get_smoke_config(arch)
    if cfg.moe is not None:
        cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, capacity_factor=64.0))
    # 4 periods so a 2-stage split can be heterogeneous
    cfg = cfg.replace(n_layers=4 * len(cfg.pattern))
    B, S = 8, 64
    mesh_prod = Mesh(np.array(devices).reshape(2, 4), ("data", "model"))

    table = LayerTable.from_model_config(cfg, S)
    prof = Profile.analytic(table, env_d().sorted_by_memory(), max_batch=B)
    plan = plan_hpp(prof, B, micro_batch=2, arch=arch, allowed_stages={2})
    ts, lowered = plan_to_train_step(plan, prof, cfg, mesh_prod)

    key = jax.random.PRNGKey(0)
    ds = SyntheticLM(cfg.vocab_size, S, n_codebooks=cfg.n_codebooks,
                     prefix_len=cfg.prefix_len, prefix_dim=frontend_dim(cfg))
    batch_np = ds.batch(0, B)
    ref_params = init_model(key, cfg)
    loss_r, metrics_r = jax.jit(lambda p, b: local_loss_fn(p, b, cfg, ce_chunk=1024))(
        ref_params, {k: jnp.asarray(v) for k, v in batch_np.items()})

    batch = ts.shard_batch(batch_np)
    params, opt_state = init_train_state(key, ts)
    loss_d, metrics = ts.loss_fn(params, batch)
    diff = abs(float(metrics["ce"]) - float(metrics_r["ce"]))

    new_params, new_opt, l0, _ = ts.step_fn(params, opt_state, batch)
    l1, _ = ts.loss_fn(new_params, batch)
    improved = float(l1) < float(l0)

    # the planner may have chosen a uniform split — exercise a maximally
    # skewed heterogeneous one (3 periods | 1 period) explicitly
    ts2 = build_train_step(cfg, mesh_prod, global_batch=B, stage=2,
                           n_micro=4, stage_periods=((0, 3), (3, 4)))
    batch2 = ts2.shard_batch(batch_np)
    params2, _ = init_train_state(key, ts2)
    _, metrics2 = ts2.loss_fn(params2, batch2)
    diff2 = abs(float(metrics2["ce"]) - float(metrics_r["ce"]))

    ok = diff < TOL and diff2 < TOL and improved
    print(f"{arch:26s} [plan] periods={lowered.stage_periods} "
          f"M={lowered.n_micro} K_p={lowered.warmup} "
          f"y={ts.spec.shard_alloc or 'uniform'} diff={diff:.2e} "
          f"het(3|1) diff={diff2:.2e} step {float(l0):.4f}->{float(l1):.4f} "
          f"{'OK' if ok else 'FAIL'}", flush=True)
    if not ok:
        raise SystemExit(f"{arch}: planned-lowering parity {diff}/{diff2} "
                         f"improved={improved}")
    return diff


def run_replay(arch: str, devices) -> float:
    """Live pipeline replay (§3.4) end-to-end on the real runtime.

    Plan -> session -> train -> kill a rank mid-training -> lightweight
    replay -> keep training.  Asserts: untouched periods bit-identical
    across the migration, runtime boundary bytes reconcile exactly with the
    analytical RecoveryReport, the re-lowered step matches a
    fresh-from-scratch lowering of the new plan on identical params, and
    the loss keeps improving after recovery."""
    import numpy as _np

    from repro.configs import get_smoke_config
    from repro.core.hardware import env_d
    from repro.core.lowering import period_positions as positions
    from repro.core.planner import plan_hpp
    from repro.core.profiler import LayerTable, Profile
    from repro.data import SyntheticLM
    from repro.runtime.session import PipelineSession
    from repro.runtime.train import build_train_step_from_lowered

    cfg = get_smoke_config(arch)
    if cfg.moe is not None:
        cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, capacity_factor=64.0))
    cfg = cfg.replace(n_layers=8 * len(cfg.pattern))   # 8 periods
    B, S = 8, 64
    mesh = Mesh(np.array(devices).reshape(2, 4), ("data", "model"))
    table = LayerTable.from_model_config(cfg, S)
    prof = Profile.analytic(table, env_d().sorted_by_memory(), max_batch=B)
    plan = plan_hpp(prof, B, micro_batch=2, arch=arch, allowed_stages={2})

    session = PipelineSession(cfg, mesh, plan, prof, backup_every=2)
    session.init(jax.random.PRNGKey(0))
    ds = SyntheticLM(cfg.vocab_size, S, n_codebooks=cfg.n_codebooks,
                     prefix_len=cfg.prefix_len)
    losses = [session.step(ds.batch(s, B))[0] for s in range(4)]

    old_pos = positions(session.lowered)
    pre = [_np.asarray(jax.device_get(x))
           for x in jax.tree.leaves(session.params["periods"])]

    # fail a member of the multi-device stage: the stage survives with its
    # DP peers, so the recovery is a pure lightweight migration
    st = max(session.plan.stages, key=lambda s: len(s.group))
    assert len(st.group) > 1, session.plan.stages
    session.fail(st.group[-1])
    out = session.recover_now()
    assert out.mode == "lightweight", out.mode

    # 1) runtime boundary bytes == analytical migration inputs (exact)
    assert out.reconciliation is not None
    for rec in out.reconciliation.values():
        assert rec["table_bytes"] == rec["analytic_bytes"], rec

    # 2) untouched periods bit-identical across the arrangement swap
    new_pos = positions(session.lowered)
    post = [_np.asarray(jax.device_get(x))
            for x in jax.tree.leaves(session.params["periods"])]
    touched = set(out.migration.moved_periods) | set(out.restored_periods)
    for t in range(session.lowered.n_periods):
        if t in touched:
            continue
        for a, b in zip(pre, post):
            assert _np.array_equal(a[old_pos[t]], b[new_pos[t]]), t

    # 3) the session's re-lowered step == a fresh lowering of the new plan
    #    on identical params
    fresh = build_train_step_from_lowered(cfg, mesh, session.lowered)
    assert fresh.spec.shard_alloc == session.ts.spec.shard_alloc
    batch_np = ds.batch(100, B)
    batch = session.ts.shard_batch(batch_np)
    l_sess, m_sess = session.ts.loss_fn(session.params, batch)
    l_fresh, m_fresh = fresh.loss_fn(session.params, batch)
    d_fresh = abs(float(l_sess) - float(l_fresh))
    assert d_fresh < 1e-6, (float(l_sess), float(l_fresh))

    # 4) training keeps improving on the replayed pipeline
    losses += [session.step(ds.batch(s, B))[0] for s in range(4, 12)]
    ok = losses[-1] < losses[0]
    print(f"{arch:26s} [replay] moved={out.migration.moved_periods} "
          f"stages {len(plan.stages)}->{session.lowered.stage} "
          f"fresh-lowering diff={d_fresh:.1e} loss {losses[0]:.4f}->"
          f"{losses[-1]:.4f} {'OK' if ok else 'FAIL'}", flush=True)
    if not ok:
        raise SystemExit(f"{arch}: loss did not improve after replay "
                         f"({losses})")
    return d_fresh


def run_serve(arch: str, devices, seq_shard: bool = False, stage=None) -> float:
    """Distributed serve_step vs single-device decode logits parity."""
    from repro.configs import get_smoke_config
    from repro.models.model import decode_step, init_decode_states, init_model
    from repro.runtime.serve import build_serve_step, prepare_serve_states
    from repro.runtime.train import prepare_params
    from repro.distributed.sharding import named

    cfg = get_smoke_config(arch).replace(prefix_len=0, mtp_depth=0)
    if cfg.moe is not None:
        cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, capacity_factor=64.0))
    B, cache_len, steps = (1, 64, 6) if seq_shard else (8, 64, 6)
    mesh_prod = Mesh(np.array(devices).reshape(2, 4), ("data", "model"))
    ss = build_serve_step(cfg, mesh_prod, batch_global=B, cache_len=cache_len,
                          seq_shard=seq_shard, stage=stage)

    key = jax.random.PRNGKey(0)
    params = jax.jit(lambda k: prepare_params(k, cfg, ss.spec.plan),
                     out_shardings=named(ss.mesh, ss.param_specs))(key)
    states = jax.jit(lambda: prepare_serve_states(cfg, ss.spec.plan, B, cache_len),
                     out_shardings=named(ss.mesh, ss.state_specs))()

    ref_params = init_model(key, cfg)
    ref_states = init_decode_states(B, cache_len, cfg)
    ref_step = jax.jit(lambda p, t, pos, st: decode_step(p, t, pos, st, cfg))

    shape = (steps, B, cfg.n_codebooks) if cfg.n_codebooks > 1 else (steps, B)
    tokens = np.random.RandomState(0).randint(0, cfg.vocab_size, size=shape)
    worst = 0.0
    for t in range(steps):
        tok = jnp.asarray(tokens[t], jnp.int32)
        logits_d, states = ss.step_fn(params, tok, jnp.int32(t), states)
        logits_r, ref_states = ref_step(ref_params, tok, jnp.int32(t), ref_states)
        d = float(jnp.max(jnp.abs(jnp.asarray(logits_d) - logits_r)))
        worst = max(worst, d)
    tag = "serve-seqshard" if seq_shard else "serve"
    ok = worst < 2e-3
    print(f"{arch:26s} [{tag}] stage={ss.spec.plan.stage} tp={ss.spec.plan.tp} "
          f"max_logit_diff={worst:.2e} {'OK' if ok else 'FAIL'}", flush=True)
    if not ok:
        raise SystemExit(f"{arch} serve parity {worst}")
    return worst


def run_serve_hetero(arch: str, devices, stage=None) -> float:
    """Heterogeneous slot-split decode (build_slot_serve_step) parity.

    An unbalanced shard_alloc=(3, 1) with staggered slot admission must
    reproduce the uniform lockstep single-device decode logits row-for-row:
    slot s admitted at wall step ``delay[s]`` decodes position p at wall
    step ``delay[s] + p`` with identical logits.  Also asserts padded slot
    rows return exactly-zero logits (the sampling-head mask) and that the
    per-row reset wipes recurrent state on admission (the staggered rows
    would diverge without it on RWKV/Mamba archs)."""
    from repro.configs import get_smoke_config
    from repro.models.model import decode_step, init_decode_states, init_model
    from repro.runtime.continuous import slot_rows
    from repro.runtime.serve import build_slot_serve_step, prepare_serve_states
    from repro.runtime.train import prepare_params
    from repro.distributed.sharding import named

    cfg = get_smoke_config(arch).replace(prefix_len=0, mtp_depth=0)
    if cfg.n_codebooks > 1:
        print(f"{arch:26s} [serve-hetero] skipped (multi-codebook)", flush=True)
        return 0.0
    if cfg.moe is not None:
        cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, capacity_factor=64.0))
    alloc, cache_len, steps = (3, 1), 64, 6
    delay = (0, 1, 2, 1)                     # admission wall-step per slot
    mesh_prod = Mesh(np.array(devices).reshape(2, 4), ("data", "model"))
    ss = build_slot_serve_step(cfg, mesh_prod, cache_len=cache_len,
                               shard_alloc=alloc, stage=stage)
    rows = slot_rows(alloc)
    B_live, B_pad = len(rows), ss.spec.batch_global

    key = jax.random.PRNGKey(0)
    params = jax.jit(lambda k: prepare_params(k, cfg, ss.spec.plan),
                     out_shardings=named(ss.mesh, ss.param_specs))(key)
    states = jax.jit(
        lambda: prepare_serve_states(cfg, ss.spec.plan, B_pad, cache_len),
        out_shardings=named(ss.mesh, ss.state_specs))()

    ref_params = init_model(key, cfg)
    ref_states = init_decode_states(B_live, cache_len, cfg)
    ref_step = jax.jit(lambda p, t, pos, st: decode_step(p, t, pos, st, cfg))

    tokens = np.random.RandomState(0).randint(0, cfg.vocab_size,
                                              size=(B_live, steps))
    ref_logits = []
    for t in range(steps):
        lg, ref_states = ref_step(ref_params, jnp.asarray(tokens[:, t]),
                                  jnp.int32(t), ref_states)
        ref_logits.append(np.asarray(lg))

    worst, pad_max = 0.0, 0.0
    for w in range(steps + max(delay)):
        tok = np.zeros(B_pad, np.int64)
        pos = np.zeros(B_pad, np.int64)
        reset = np.zeros(B_pad, bool)
        live = {}
        for s, row in enumerate(rows):
            p = w - delay[s]
            if p < 0 or p >= steps:
                reset[row] = True            # idle slots stay wiped
                continue
            tok[row], pos[row], reset[row] = tokens[s, p], p, p == 0
            live[s] = (row, p)
        logits, states = ss.step_fn(params, jnp.asarray(tok, jnp.int32),
                                    jnp.asarray(pos, jnp.int32),
                                    jnp.asarray(reset), states)
        logits = np.asarray(jax.device_get(logits))
        for s, (row, p) in live.items():
            worst = max(worst, float(np.max(np.abs(logits[row] -
                                                   ref_logits[p][s]))))
        for row in range(B_pad):
            if row not in rows:
                pad_max = max(pad_max, float(np.max(np.abs(logits[row]))))
    ok = worst < TOL and pad_max == 0.0
    print(f"{arch:26s} [serve-hetero] y={alloc} stage={ss.spec.plan.stage} "
          f"tp={ss.spec.plan.tp} max_logit_diff={worst:.2e} "
          f"pad_logits={pad_max:.1e} {'OK' if ok else 'FAIL'}", flush=True)
    if not ok:
        raise SystemExit(f"{arch} serve-hetero parity {worst} pad={pad_max}")
    return worst


def main():
    args = [a for a in sys.argv[1:] if not a.startswith("--")]
    serve = "--serve" in sys.argv
    serve_hetero = "--serve-hetero" in sys.argv
    seq_shard = "--seq-shard" in sys.argv
    planned = "--plan" in sys.argv
    replay = "--replay" in sys.argv
    hetero = "--hetero" in sys.argv
    async_mode = "--async" in sys.argv
    compress = "--compress" in sys.argv
    stage = 2 if "--stage2" in sys.argv else None
    archs = args or DEFAULT_ARCHS
    devices = jax.devices()
    assert len(devices) >= 8, "needs 8 host devices"
    for arch in archs:
        if serve_hetero:
            run_serve_hetero(arch, devices[:8], stage=stage)
        elif serve:
            run_serve(arch, devices[:8], seq_shard=seq_shard)
        elif planned:
            run_arch_planned(arch, devices[:8])
        elif replay:
            run_replay(arch, devices[:8])
        elif hetero:
            run_arch_hetero(arch, devices[:8])
        elif async_mode:
            run_async(arch, devices[:8])
        elif compress:
            run_compress(arch, devices[:8])
        else:
            run_arch(arch, devices[:8])
    print("ALL OK")


if __name__ == "__main__":
    main()
