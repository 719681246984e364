"""Plan -> runtime lowering: round-trip invariants and simulator
consistency (pure-python; the jax end-to-end path is covered by
tests/test_distributed.py::test_train_planned_lowering)."""

import dataclasses
import json
from pathlib import Path

import hypothesis.strategies as hst
import numpy as np
import pytest
from hypothesis import assume, given, settings

from repro.configs import get_config
from repro.core.costmodel import kp_policy
from repro.core.hardware import env_b, env_c, env_d, env_v5e
from repro.core.lowering import (LoweredPlan, LoweringError,
                                 check_against_simulator, even_periods,
                                 lower_micro_alloc, lower_plan, snap_plan)
from repro.core.planner import Plan, plan_gpipe, plan_hpp
from repro.core.profiler import LayerTable, Profile
from repro.core.replay import _plan_from_cuts
from repro.core.schedule import max_inflight, schedule_orders
from repro.core.simulator import reprice_plan, simulate
from repro.data import pack_batch, pack_indices
from repro.distributed.mesh import MeshPlan
from repro.models import AttentionConfig, LayerSpec, ModelConfig
from repro.runtime.pipeline import TrainSpec, slot_counts

PHI3_L8 = (Path(__file__).resolve().parents[1]
           / "benchmarks/chip/configs/phi3-mini-l8.json")


@pytest.fixture(scope="module")
def setup():
    cfg = ModelConfig(name="t", n_layers=8, d_model=256, vocab_size=8000,
                      d_ff=1024,
                      attn=AttentionConfig(n_heads=4, n_kv_heads=4, head_dim=64),
                      pattern=(LayerSpec(),))
    table = LayerTable.from_model_config(cfg, seq_len=128)
    prof = Profile.analytic(table, env_d().sorted_by_memory(), max_batch=32)
    plan = plan_hpp(prof, 64, 8, arch="t")
    return cfg, prof, plan


def test_round_trip_invariants(setup):
    cfg, prof, plan = setup
    low = lower_plan(plan, cfg)
    P = len(plan.stages)
    n_periods = cfg.n_layers // len(cfg.pattern)

    # stage period ranges partition [0, n_periods)
    assert low.stage_periods[0][0] == 0
    assert low.stage_periods[-1][1] == n_periods
    for (a, b), (c, d) in zip(low.stage_periods[:-1], low.stage_periods[1:]):
        assert b == c and a < b and c < d

    # allocations: per-stage sums = micro-batch; rounds cover the batch
    for alloc in low.micro_alloc:
        assert sum(alloc) == low.micro_batch
    assert low.n_micro * low.micro_batch == plan.global_batch == low.global_batch

    # warm-up depths come from the schedule policy and match the plan
    assert low.warmup == tuple(kp_policy(P, p) for p in range(P))
    assert low.warmup == tuple(st.k_p for st in plan.stages)

    # runtime tick counts
    assert low.forward_ticks == low.n_micro + P - 1
    assert low.total_ticks == 2 * low.forward_ticks


def test_lowered_orders_match_schedule(setup):
    cfg, prof, plan = setup
    low = lower_plan(plan, cfg)
    assert low.orders() == schedule_orders(low.stage, low.n_micro, "ours")
    assert low.peak_inflight() == tuple(
        max_inflight(o) for o in low.orders())
    # 1F1B bounds resident activations by K_p; GPipe by M
    assert all(i <= min(max(1, k), low.n_micro)
               for i, k in zip(low.peak_inflight(), low.warmup))
    assert all(i == low.n_micro for i in low.peak_inflight("gpipe"))


def test_simulator_consistency(setup):
    cfg, prof, plan = setup
    # asserts: per-stage op counts, unit-cost makespan == tick_makespan,
    # peak in-flight == min(max(1, K_p), M), Eq. 3 memory bound
    sim = check_against_simulator(lower_plan(plan, cfg), plan, prof)
    assert sim.makespan > 0


def test_gpipe_ticks_equal_runtime_scan(setup):
    """The runtime executes a GPipe-ordered scan: M + P - 1 forward ticks
    and the grad-reversed backward, 2(M + P - 1) in total — the unit-cost
    GPipe schedule has the same makespan."""
    cfg, prof, plan = setup
    low = lower_plan(plan, cfg)
    assert low.tick_makespan("gpipe") == low.total_ticks


def test_memory_bound_tracks_simulator(setup):
    """The Eq. 3 bound of the lowered cut covers the simulated peaks of the
    plan as deployed (on that cut, re-priced)."""
    cfg, prof, plan = setup
    low = lower_plan(plan, cfg)
    sim = simulate(reprice_plan(snap_plan(plan, low, prof.table.L), prof),
                   prof)
    bound = low.memory_bound(prof)
    assert set(bound) == set(sim.peak_mem)
    for d in bound:
        assert sim.peak_mem[d] <= bound[d] * (1 + 1e-6)


def test_infeasible_mesh_raises(setup):
    cfg, prof, plan = setup
    P = len(plan.stages)
    bad_axis = P + 1 if P > 1 else 3       # never divisible by P... unless P=1
    if P == 1:
        pytest.skip("single-stage plan divides everything")
    assert bad_axis % P != 0
    with pytest.raises(LoweringError):
        lower_plan(plan, cfg, model_axis=bad_axis)


def test_too_many_stages_raises(setup):
    cfg, prof, plan = setup
    small = cfg.replace(n_layers=1)        # 1 period < plan stages
    if len(plan.stages) == 1:
        pytest.skip("single-stage plan fits any model")
    with pytest.raises(LoweringError):
        lower_plan(plan, small)


def test_warmup_mismatch_raises(setup):
    cfg, prof, plan = setup
    if len(plan.stages) == 1:
        pytest.skip("K_p is trivially 1")
    bad = dataclasses.replace(
        plan, stages=tuple(
            dataclasses.replace(st, k_p=st.k_p + 1) for st in plan.stages))
    with pytest.raises(LoweringError):
        lower_plan(bad, cfg)


def _lp_alloc(micro_alloc, micro_batch):
    """Minimal LoweredPlan carrying only allocation structure."""
    P = len(micro_alloc)
    return LoweredPlan(
        arch="t", stage=P, n_micro=4, micro_batch=micro_batch,
        global_batch=4 * micro_batch, n_periods=P,
        stage_periods=tuple((p, p + 1) for p in range(P)),
        stage_layers=tuple((0, 0) for _ in range(P)),
        device_groups=tuple(tuple(range(len(a))) for a in micro_alloc),
        micro_alloc=tuple(tuple(a) for a in micro_alloc),
        warmup=tuple(kp_policy(P, p) for p in range(P)))


def test_lower_micro_alloc_direct_and_blocks():
    # group size == dp: exact
    assert lower_micro_alloc(_lp_alloc([(3, 1), (3, 1)], 4), 2) == (3, 1)
    # group larger than dp: contiguous device blocks aggregate
    assert lower_micro_alloc(_lp_alloc([(2, 1, 1)], 4), 2) == (2, 2)
    assert lower_micro_alloc(_lp_alloc([(4, 1, 1, 0)], 6), 2) == (5, 1)
    # group smaller than dp: a device's share splits across its shards
    assert lower_micro_alloc(_lp_alloc([(5,)], 5), 2) == (3, 2)
    assert lower_micro_alloc(_lp_alloc([(4, 2)], 6), 4) == (2, 2, 1, 1)


def test_lower_micro_alloc_disagreeing_stages():
    # disagreeing stages: largest-remainder rounding of the mean, still
    # summing to the micro-batch
    out = lower_micro_alloc(_lp_alloc([(4, 0), (2, 2)], 4), 2)
    assert sum(out) == 4 and out == (3, 1)
    out = lower_micro_alloc(_lp_alloc([(3, 1), (1, 3)], 4), 2)
    assert sum(out) == 4 and out == (2, 2)
    # agreement after projection collapses exactly
    assert lower_micro_alloc(_lp_alloc([(2, 2), (2, 1, 1)], 4), 2) == (2, 2)


def test_lower_micro_alloc_sum_preserved():
    # explicit cases; the hypothesis suite fuzzes this in
    # tests/test_allocation_props.py
    for allocs, dp in [
            ([(7, 3, 2), (6, 4, 2)], 4),
            ([(1, 1, 1)], 2),
            ([(5, 0), (0, 5)], 3),
    ]:
        mb = sum(allocs[0])
        out = lower_micro_alloc(_lp_alloc(allocs, mb), dp)
        assert len(out) == dp and sum(out) == mb and min(out) >= 0


def test_pack_batch_round_trip():
    """Every input sample appears exactly once at its indexed slot; padding
    slots are zero; valid counts match the allocation."""
    alloc, M = (3, 1), 4
    mb, b_max = sum(alloc), max(alloc)
    B = M * mb
    batch = {"tokens": np.arange(B * 5, dtype=np.int32).reshape(B, 5) + 1}
    out = pack_batch(batch, alloc, M)
    idx, valid = pack_indices(alloc, M)
    assert out["tokens"].shape == (len(alloc) * M * b_max, 5)
    assert valid.sum() == B
    got = out["tokens"].reshape(len(alloc), M, b_max, 5)
    seen = []
    for d in range(len(alloc)):
        for m in range(M):
            for b in range(b_max):
                if valid[d, m, b]:
                    assert (got[d, m, b] == batch["tokens"][idx[d, m, b]]).all()
                    seen.append(idx[d, m, b])
                else:
                    assert (got[d, m, b] == 0).all()
    assert sorted(seen) == list(range(B))
    # micro-batch m draws exactly from input rows [m*mb, (m+1)*mb)
    for m in range(M):
        rows = sorted(idx[d, m, b] for d in range(len(alloc))
                      for b in range(b_max) if valid[d, m, b])
        assert rows == list(range(m * mb, (m + 1) * mb))


def test_pack_batch_rejects_bad_shapes():
    with pytest.raises(ValueError):
        pack_batch({"tokens": np.zeros((7, 2))}, (3, 1), 2)
    with pytest.raises(ValueError):
        pack_indices((0, 0), 2)


def test_eq8_stale_steps_raise(setup):
    """check_against_simulator rejects a plan whose step times went stale
    against its allocations (Eq. 8 consistency)."""
    cfg, prof, plan = setup
    low = lower_plan(plan, cfg)
    steps = tuple(
        dataclasses.replace(s, ef=s.ef * 1.5) if s.kind == "exec" else s
        for s in plan.steps)
    bad = dataclasses.replace(plan, steps=steps)
    with pytest.raises(AssertionError):
        check_against_simulator(low, bad, prof)


def test_simulator_device_busy_scales_with_allocation(setup):
    """Per-device busy time is M * (t_f + t_b) at the device's allocated
    sample count, bounded by the stage's lockstep busy time."""
    cfg, prof, plan = setup
    sim = simulate(plan, prof)
    M = plan.n_micro
    assert set(sim.device_busy) == {d for st in plan.stages for d in st.group}
    for p, st in enumerate(plan.stages):
        i, j = st.layers
        for d, y in zip(st.group, st.alloc):
            t_dev = M * (prof.t_fwd(d, y, i, j) + prof.t_bwd(d, y, i, j))
            assert sim.device_busy[d] == pytest.approx(t_dev)
            assert sim.device_busy[d] <= sim.stage_busy[p] * (1 + 1e-9)
            assert 0.0 <= sim.device_util(d) <= 1.0


def test_heterogeneous_cluster_envs(setup):
    """Lowering holds across planners and environments."""
    cfg, _, _ = setup
    table = LayerTable.from_model_config(cfg, seq_len=128)
    for env in (env_b, env_d):
        prof = Profile.analytic(table, env().sorted_by_memory(), max_batch=32)
        for mk in (lambda: plan_hpp(prof, 64, 8, arch="t"),
                   lambda: plan_gpipe(prof, 64, 8, arch="t", n_stages=2)):
            plan = mk()
            low = lower_plan(plan, cfg)
            check_against_simulator(low, plan, prof)


# ---------------------------------------------------------------------------
# The deployed period cut: the most even split of the period stack
# ---------------------------------------------------------------------------


def _small_cfg(n_layers):
    return ModelConfig(name="p", n_layers=n_layers, d_model=128,
                       vocab_size=4000, d_ff=512,
                       attn=AttentionConfig(n_heads=4, n_kv_heads=4,
                                            head_dim=32),
                       pattern=(LayerSpec(),))


def _plan_on_cuts(prof, groups, cuts):
    """A plan on the given table cuts and device groups, priced by
    Algorithm 1 within each stage (global batch 8, micro-batch 2)."""
    return _plan_from_cuts(Plan("p", (), (), 2, 4, 0.0), prof, groups, cuts,
                           planner="test")


def _computed_slots(cfg, low, model_axis):
    spec = TrainSpec(cfg=cfg, plan=MeshPlan(pod=1, data=1, stage=low.stage,
                                            tp=model_axis // low.stage),
                     n_micro=low.n_micro, stage_periods=low.stage_periods)
    return slot_counts(spec)


@hst.composite
def planner_cuts(draw):
    """A planner-shaped plan: any stage count dividing a model axis of at
    most 8, any contiguous table cut, on a homogeneous v5e host or a
    heterogeneous edge cluster (the paper's env C)."""
    n = draw(hst.integers(1, 32))
    axis = draw(hst.integers(1, 8))
    P = draw(hst.sampled_from([d for d in range(1, axis + 1)
                               if axis % d == 0]))
    hetero = draw(hst.booleans())
    cluster = env_c() if hetero else env_v5e(axis)
    assume(P <= n and P <= len(cluster.devices))
    L = n + 2
    interior = sorted(draw(hst.sets(hst.integers(1, L - 1), min_size=P - 1,
                                    max_size=P - 1)))
    devs = len(cluster.devices)
    bounds = [p * devs // P for p in range(P + 1)]
    groups = [tuple(range(bounds[p], bounds[p + 1])) for p in range(P)]
    return n, axis, cluster, groups, [0] + interior + [L]


@settings(max_examples=200, deadline=None)
@given(planner_cuts())
def test_lowered_cut_is_most_even(case):
    """Every lowered cut is contiguous, covers all periods, and has shares
    that differ by at most one, so the padded scan computes
    P * ceil(n / P) periods per tick; the simulator cross-check holds on the
    deployed cut."""
    n, axis, cluster, groups, cuts = case
    cfg = _small_cfg(n)
    prof = Profile.analytic(LayerTable.from_model_config(cfg, seq_len=64),
                            cluster.sorted_by_memory(), max_batch=8)
    plan = _plan_on_cuts(prof, groups, cuts)
    low = lower_plan(plan, cfg, axis)
    P = low.stage
    assert low.stage_periods[0][0] == 0 and low.stage_periods[-1][1] == n
    for (_, b), (c, _) in zip(low.stage_periods[:-1], low.stage_periods[1:]):
        assert b == c
    shares = [j - i for i, j in low.stage_periods]
    assert min(shares) >= 1 and max(shares) - min(shares) <= 1
    assert low.k_per_stage == -(-n // P)
    real, computed = _computed_slots(cfg, low, axis)
    assert real == n * low.n_micro
    assert computed == P * -(-n // P) * low.forward_ticks
    # the planner's own cut is kept beside it, and its costs are untouched
    assert len(low.planner_periods) == P
    assert [st.layers for st in plan.stages] == [
        (cuts[p], cuts[p + 1]) for p in range(P)]
    assert snap_plan(plan, low, prof.table.L).stages[0].layers == \
        low.stage_layers[0]
    check_against_simulator(low, plan, prof)


@pytest.mark.parametrize("cuts, groups, axis", [
    ([0, 10], [(0, 1, 2, 3)], 4),                      # P = 1
    ([0, 3, 5, 7, 10], [(0,), (1,), (2,), (3,)], 4),   # 2|2|2|2
    ([0, 4, 7, 10], [(0,), (1,), (2,)], 3),            # 3|3|2
    ([0, 3, 6, 10], [(0,), (1,), (2,)], 3),            # 2|3|3
])
def test_even_planner_cut_passes_through(cuts, groups, axis):
    """An already-even planner cut, and any single-stage plan, is deployed
    unchanged."""
    cfg = _small_cfg(cuts[-1] - 2)
    prof = Profile.analytic(LayerTable.from_model_config(cfg, seq_len=64),
                            env_v5e(axis), max_batch=8)
    plan = _plan_on_cuts(prof, groups, cuts)
    low = lower_plan(plan, cfg, axis)
    assert low.stage_periods == low.planner_periods
    assert low.stage_layers == tuple(st.layers for st in plan.stages)
    assert snap_plan(plan, low, prof.table.L) == plan


def test_even_periods_gives_extras_to_the_largest_planner_shares():
    assert even_periods(((0, 3), (3, 5), (5, 7), (7, 8)), 8) == \
        ((0, 2), (2, 4), (4, 6), (6, 8))
    # 1|3|3 over 7: the one larger share goes to the first largest stage
    assert even_periods(((0, 1), (1, 4), (4, 7)), 7) == \
        ((0, 2), (2, 5), (5, 7))
    # 3|3|3|1 already pads to 3, but is not the most even: 3|3|2|2
    assert even_periods(((0, 3), (3, 6), (6, 9), (9, 10)), 10) == \
        ((0, 3), (3, 6), (6, 8), (8, 10))
    assert even_periods(((0, 5),), 5) == ((0, 5),)


def test_four_chip_job_lowers_onto_the_even_cut():
    """The calls of the four-chip benchmark job (analytic v5e host of four,
    stage counts dividing the model axis, batch 8 in micro-batches of 1) on
    phi3-mini at 8 layers: the planner's Eq. 4 cut 3|2|2|1 is deployed as
    2|2|2|2, and the padded scan computes 88 slots for 64 real ones."""
    conf = json.loads(PHI3_L8.read_text())
    model = dict(conf["model"])
    base = get_config(conf["arch"])
    cfg = base.replace(attn=dataclasses.replace(base.attn,
                                                **model.pop("attn")), **model)
    table = LayerTable.from_model_config(cfg, 2048)
    prof = Profile.analytic(table, env_v5e(4).sorted_by_memory(),
                            max_batch=8)
    plan = plan_hpp(prof, 8, 1, arch=cfg.name, allowed_stages={1, 2, 4},
                    intra_opt="auto", staleness=0, compress=None)
    low = lower_plan(plan, cfg, 4)
    assert low.planner_periods == ((0, 3), (3, 5), (5, 7), (7, 8))
    assert low.stage_periods == ((0, 2), (2, 4), (4, 6), (6, 8))
    assert _computed_slots(cfg, low, 4) == (64, 88)
    # the Plan keeps the planner's Eq. 4 cut and latency
    assert [st.layers for st in plan.stages] == [(0, 4), (4, 6), (6, 8),
                                                 (8, 10)]
    assert plan.latency == pytest.approx(11.635, rel=1e-3)
    check_against_simulator(low, plan, prof)
