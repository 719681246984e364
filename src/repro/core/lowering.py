"""Lower an Asteroid ``Plan`` (Algorithm 2 output) into the pipeline runtime.

The planner reasons about an edge cluster in *layer-table* coordinates:
stages are layer ranges ``[i, j)`` over ``embed + n_layers + head`` pseudo
layers, device groups are ranks into the profiled cluster, and micro-batch
allocations are per-device sample counts.  The shard_map runtime
(``repro.runtime``) executes in *mesh* coordinates: a refined
``(pod, data, stage, tp)`` mesh whose ``stage`` axis slices the stacked
period params, with ``M`` micro-batches streamed through a circular
ppermute pipeline.

``lower_plan`` translates between the two worlds:

* stage count        -> ``MeshPlan.stage`` (must divide the mesh model axis),
* layer ranges       -> per-stage *period* ranges: the planner's cuts
                        snapped to period boundaries (periods are the
                        runtime's atomic unit), then re-cut to the most even
                        split, since the runtime pads every stage to the
                        largest share (``even_periods``),
* ``Plan.n_micro``   -> the runtime's micro-batch count ``M``,
* per-stage warm-up  -> K_p from ``core.schedule`` (validated against the
                        plan's own ``StagePlan.k_p``),
* ``micro_alloc``    -> per-data-shard sample counts (``lower_micro_alloc``):
                        Algorithm 1's heterogeneous intra-stage allocation,
                        realized by padding every shard's micro-batch to
                        ``B_max = max_d y_d`` with a static validity mask —
                        the batch-dimension analogue of how
                        ``arrange_periods`` realizes heterogeneous layer
                        splits.

``plan_to_train_step`` then builds the runnable distributed train step, and
``check_against_simulator`` cross-checks the lowered schedule against the
discrete-event simulator: per-stage op counts, the unit-cost makespan in
ticks, and the O(K_p) resident-activation bound (DESIGN.md §2, §4).

The *replay* half of the module makes a lowered pipeline re-lowerable while
training (DESIGN.md §7): ``relower`` lowers a replacement ``Plan`` against
an existing ``LoweredPlan``'s runtime, ``migrate_params`` /
``migrate_opt_state`` re-arrange the stacked period params (and optimizer
moments, with the same index map) from the old stage split to the new one,
and ``reconcile_migration`` checks the resulting per-boundary bytes against
the analytical ``RecoveryReport`` a ``lightweight_replay`` produced.
"""

from __future__ import annotations

import dataclasses

from .costmodel import kp_policy, stage_memory
from .planner import Plan
from .profiler import Profile
from .schedule import max_inflight, schedule_orders
from .simulator import SimResult, reprice_plan, simulate


class LoweringError(RuntimeError):
    """The plan cannot be realized on the requested runtime mesh."""


@dataclasses.dataclass(frozen=True)
class LoweredPlan:
    """Runtime-coordinate view of an Asteroid ``Plan``."""

    arch: str
    stage: int                                  # pipeline depth P
    n_micro: int                                # micro-batches per round M
    micro_batch: int                            # samples per micro-batch
    global_batch: int
    n_periods: int                              # real periods in the model
    stage_periods: tuple[tuple[int, int], ...]  # deployed period range [i, j)
    stage_layers: tuple[tuple[int, int], ...]   # deployed table layer ranges
    device_groups: tuple[tuple[int, ...], ...]  # edge-cluster ranks (Plan)
    micro_alloc: tuple[tuple[int, ...], ...]    # per-device sample allocation
    warmup: tuple[int, ...]                     # K_p per stage
    # the planner's Eq. 4 cut snapped to periods, before the even re-cut
    planner_periods: tuple[tuple[int, int], ...] = ()

    @property
    def k_per_stage(self) -> int:
        """Uniform periods-per-stage slice width (max range, zero-padded)."""
        return max(j - i for i, j in self.stage_periods)

    @property
    def forward_ticks(self) -> int:
        """Scan length of the runtime's circular forward pipeline."""
        return self.n_micro + self.stage - 1

    @property
    def total_ticks(self) -> int:
        """Forward scan + its grad-reversed backward scan."""
        return 2 * self.forward_ticks

    def orders(self, policy: str = "ours"):
        """Per-stage 1F1B op orders for this plan's (P, M)."""
        return schedule_orders(self.stage, self.n_micro, policy)

    def peak_inflight(self, policy: str = "ours") -> tuple[int, ...]:
        """Peak resident micro-batches per stage under the op orders."""
        return tuple(max_inflight(o) for o in self.orders(policy))

    def memory_bound(self, profile: Profile) -> dict[int, float]:
        """Eq. (3) per-device peak bytes implied by the lowered schedule."""
        out: dict[int, float] = {}
        for st_layers, group, alloc, k in zip(self.stage_layers,
                                              self.device_groups,
                                              self.micro_alloc, self.warmup):
            for d, y in zip(group, alloc):
                out[d] = stage_memory(profile.table, *st_layers, y, k,
                                      self.n_micro)
        return out

    def tick_makespan(self, policy: str = "ours") -> int:
        """Schedule completion time in unit ticks (ef = eb = 1, zero comm).

        An independent list-scheduling implementation of the simulator's
        dependency rules, used to cross-validate the two.
        """
        P, M = self.stage, self.n_micro
        orders = self.orders(policy)
        f_done = [[None] * M for _ in range(P)]
        b_done = [[None] * M for _ in range(P)]
        idx = [0] * P
        free = [0] * P
        remaining = sum(len(o) for o in orders)
        while remaining:
            progressed = False
            for p in range(P):
                while idx[p] < len(orders[p]):
                    op = orders[p][idx[p]]
                    if op.kind == "F":
                        dep = 0 if p == 0 else f_done[p - 1][op.micro]
                    elif p == P - 1:
                        dep = f_done[p][op.micro]
                    else:
                        dep = b_done[p + 1][op.micro]
                    if dep is None:
                        break
                    end = max(free[p], dep) + 1
                    free[p] = end
                    (f_done if op.kind == "F" else b_done)[p][op.micro] = end
                    idx[p] += 1
                    remaining -= 1
                    progressed = True
            if not progressed:
                raise LoweringError("deadlocked schedule (invalid op orders)")
        return max(free)


# ---------------------------------------------------------------------------
# Plan -> runtime coordinates
# ---------------------------------------------------------------------------


def snap_to_periods(stage_layers, n_layers: int, pattern_len: int,
                    n_periods: int) -> tuple[tuple[int, int], ...]:
    """Snap table-coordinate layer cuts to period boundaries.

    Table layout: index 0 = embed, 1..n_layers = real layers, L-1 = head.
    Interior cuts land on the nearest period boundary, kept strictly
    monotone so every stage owns >= 1 period.
    """
    P = len(stage_layers)
    if P > n_periods:
        raise LoweringError(
            f"plan has {P} stages but the model only has {n_periods} periods")
    cuts = [0]
    for s, (i, j) in enumerate(stage_layers[:-1]):
        r = min(max(j - 1, 0), n_layers)           # cut in real-layer coords
        per = round(r / pattern_len)
        # strictly monotone, leaving >= 1 period for each remaining stage
        per = max(per, cuts[-1] + 1)
        per = min(per, n_periods - (P - 1 - s))
        cuts.append(per)
    cuts.append(n_periods)
    return tuple((cuts[p], cuts[p + 1]) for p in range(P))


def even_periods(planner_periods, n_periods: int) -> tuple[tuple[int, int], ...]:
    """The period cut the padded tick scan runs fastest.

    Every stage computes the largest share ``k`` of periods on every tick
    (``arrange_periods`` pads the others with zero periods), so a step costs
    ``k`` period slots per tick whatever the split; ``k`` is least,
    ``ceil(n_periods / P)``, exactly when the shares differ by at most one.
    The ``n_periods % P`` larger shares go to the stages with the largest
    shares in ``planner_periods`` (the earlier on a tie), so a cut that is
    already that even is returned unchanged.
    """
    P = len(planner_periods)
    q, r = divmod(n_periods, P)
    by_share = sorted(range(P), key=lambda p: (planner_periods[p][0]
                                               - planner_periods[p][1], p))
    larger = set(by_share[:r])
    cuts = [0]
    for p in range(P):
        cuts.append(cuts[-1] + q + (p in larger))
    return tuple(zip(cuts[:-1], cuts[1:]))


def period_layers(stage_periods, pattern_len: int,
                  L: int) -> tuple[tuple[int, int], ...]:
    """Table layer ranges of a period cut: the first stage also owns the
    embedding (table layer 0), the last the head (table layer ``L - 1``)."""
    cuts = [0] + [1 + j * pattern_len for _, j in stage_periods[:-1]] + [L]
    return tuple(zip(cuts[:-1], cuts[1:]))


def lower_plan(plan: Plan, cfg, model_axis: int | None = None) -> LoweredPlan:
    """Translate ``plan`` into runtime coordinates for ``cfg``.

    ``model_axis``: size of the production mesh's model axis; when given the
    stage count must divide it (tp = model_axis / stage).

    Validates the plan's internal contract before anything compiles: stage
    ranges contiguous, per-stage warm-ups equal to the schedule's
    ``kp_policy`` K_p (the Eq. 3 memory bound assumes them), allocations
    summing to the micro-batch, ``n_micro * micro_batch == global_batch``.
    """
    P = len(plan.stages)
    if model_axis is not None and model_axis % P != 0:
        raise LoweringError(
            f"stage count {P} does not divide the mesh model axis "
            f"{model_axis}; re-plan with max_stages set to a divisor")
    if cfg.n_layers % len(cfg.pattern) != 0:
        raise LoweringError(
            f"n_layers {cfg.n_layers} not a multiple of the pattern "
            f"({len(cfg.pattern)})")
    n_periods = cfg.n_layers // len(cfg.pattern)

    planner_layers = tuple(st.layers for st in plan.stages)
    for (a, b), (c, _) in zip(planner_layers[:-1], planner_layers[1:]):
        if b != c:
            raise LoweringError(f"stage layer ranges not contiguous: {b} != {c}")

    planner_periods = snap_to_periods(planner_layers, cfg.n_layers,
                                      len(cfg.pattern), n_periods)
    stage_periods = even_periods(planner_periods, n_periods)

    warmup = tuple(kp_policy(P, p) for p in range(P))
    for p, st in enumerate(plan.stages):
        if st.k_p != warmup[p]:
            raise LoweringError(
                f"stage {p} warm-up {st.k_p} != schedule K_p {warmup[p]}")
        if sum(st.alloc) != plan.micro_batch:
            raise LoweringError(
                f"stage {p} allocation {st.alloc} does not sum to the "
                f"micro-batch {plan.micro_batch}")
    if plan.n_micro * plan.micro_batch != plan.global_batch:
        raise LoweringError("n_micro * micro_batch != global_batch")

    return LoweredPlan(
        arch=plan.arch, stage=P, n_micro=plan.n_micro,
        micro_batch=plan.micro_batch, global_batch=plan.global_batch,
        n_periods=n_periods, stage_periods=stage_periods,
        stage_layers=period_layers(stage_periods, len(cfg.pattern),
                                   cfg.n_layers + 2),
        device_groups=tuple(st.group for st in plan.stages),
        micro_alloc=tuple(st.alloc for st in plan.stages), warmup=warmup,
        planner_periods=planner_periods)


# ---------------------------------------------------------------------------
# Micro-batch allocation -> data-shard coordinates
# ---------------------------------------------------------------------------


def _project_alloc(alloc: tuple[int, ...], dp: int) -> tuple[int, ...]:
    """Project one stage's per-device allocation onto ``dp`` data shards.

    Devices keep the planner's order.  With more devices than shards,
    contiguous device blocks aggregate onto one shard; with fewer, each
    device's share is split evenly across its block of shards (that device's
    work is data-parallel over several mesh columns).
    """
    G = len(alloc)
    if G == dp:
        return tuple(alloc)
    if G > dp:
        bounds = [s * G // dp for s in range(dp + 1)]
        return tuple(sum(alloc[bounds[s]:bounds[s + 1]]) for s in range(dp))
    out = [0] * dp
    for g, y in enumerate(alloc):
        lo, hi = g * dp // G, (g + 1) * dp // G
        q, r = divmod(y, hi - lo)
        for k in range(hi - lo):
            out[lo + k] = q + (1 if k < r else 0)
    return tuple(out)


def lower_micro_alloc(lowered: LoweredPlan, dp_shards: int) -> tuple[int, ...]:
    """Collapse the plan's per-stage device allocations (Algorithm 1 /
    Eq. 9) into the single per-data-shard sample allocation the shard_map
    runtime executes.

    In mesh coordinates every stage's intra-stage group is the *same* set of
    ``dp_shards`` data columns (the mesh is rectangular), and the circular
    pipeline never re-splits samples across the data axis between stages —
    so Algorithm 1's per-stage allocations are projected onto ``dp_shards``
    slots (``_project_alloc``) and, when stages disagree, combined by
    largest-remainder rounding of their mean.  When every stage projects to
    the same vector the result is exact; the returned counts always sum to
    ``lowered.micro_batch``.
    """
    if dp_shards < 1:
        raise LoweringError(f"dp_shards must be >= 1, got {dp_shards}")
    mb = lowered.micro_batch
    projs = [_project_alloc(a, dp_shards) for a in lowered.micro_alloc]
    if all(p == projs[0] for p in projs):
        out = projs[0]
    else:
        mean = [sum(p[d] for p in projs) / len(projs)
                for d in range(dp_shards)]
        base = [int(x) for x in mean]
        rem = mb - sum(base)
        order = sorted(range(dp_shards), key=lambda d: (base[d] - mean[d], d))
        for d in order[:rem]:
            base[d] += 1
        out = tuple(base)
    if sum(out) != mb or any(y < 0 for y in out):
        raise LoweringError(
            f"collapsed allocation {out} does not partition the micro-batch "
            f"{mb} over {dp_shards} data shards")
    return out


# ---------------------------------------------------------------------------
# Simulator cross-check
# ---------------------------------------------------------------------------


def _unitize(plan: Plan) -> Plan:
    """Copy of ``plan`` with unit exec cost and free communication."""
    steps = tuple(
        dataclasses.replace(s, ef=1.0, eb=1.0, ta=0.0) if s.kind == "exec"
        else dataclasses.replace(s, ef=0.0, eb=0.0) for s in plan.steps)
    return dataclasses.replace(plan, steps=steps)


def check_against_simulator(lowered: LoweredPlan, plan: Plan,
                            profile: Profile, policy: str = "ours",
                            rel_tol: float = 1e-6) -> SimResult:
    """Assert the lowered schedule agrees with the discrete-event simulator.

    The simulator runs the *deployed* plan: ``plan`` on the lowered cut
    (``snap_plan``), re-priced on ``profile``.

    1. every stage executes exactly M forwards + M backwards,
    2. the simulator's makespan on a unit-cost copy of the plan equals the
       lowered schedule's tick count (two independent implementations of
       the same dependency rules),
    3. peak resident activations per stage equal ``min(max(1, K_p), M)`` —
       the O(K_p) 1F1B memory bound — and the simulator's per-device peak
       bytes stay within the Eq. (3) budget the lowering derives,
    4. ``plan``'s own stage latencies are Eq. (8): the max over the group
       of per-device times priced at the *allocated* sample counts (catches
       plans whose steps went stale against their allocations),
    5. the simulator's per-device busy times scale with allocated samples —
       ``M * (t_f(d, y_d) + t_b(d, y_d))`` exactly — and never exceed the
       lockstep stage busy time.
    Returns the (real-cost) simulation of the deployed plan for further
    inspection.
    """
    M, P = lowered.n_micro, lowered.stage

    for p, st in enumerate(s for s in plan.steps if s.kind == "exec"):
        i, j = st.layers
        ef = max(profile.t_fwd(d, y, i, j) for d, y in zip(st.group, st.alloc))
        eb = max(profile.t_bwd(d, y, i, j) for d, y in zip(st.group, st.alloc))
        assert abs(st.ef - ef) <= rel_tol * max(ef, 1e-12), (p, st.ef, ef)
        assert abs(st.eb - eb) <= rel_tol * max(eb, 1e-12), (p, st.eb, eb)

    deployed = reprice_plan(snap_plan(plan, lowered, profile.table.L),
                            profile)
    sim = simulate(deployed, profile, policy)

    ops_per_stage = [0] * P
    for (_, _, p, _) in sim.trace:
        ops_per_stage[p] += 1
    assert ops_per_stage == [2 * M] * P, (ops_per_stage, M)

    unit = simulate(_unitize(deployed), profile, policy)
    ticks = lowered.tick_makespan(policy)
    assert abs(unit.makespan - ticks) <= rel_tol * ticks, \
        (unit.makespan, ticks)

    inflight = lowered.peak_inflight(policy)
    expected = tuple(min(max(1, k), M) for k in lowered.warmup)
    assert inflight == expected, (inflight, expected)

    bound = lowered.memory_bound(profile)
    for d, peak in sim.peak_mem.items():
        assert peak <= bound[d] * (1 + rel_tol), (d, peak, bound[d])

    for p, st in enumerate(s for s in deployed.steps if s.kind == "exec"):
        i, j = st.layers
        for d, y in zip(st.group, st.alloc):
            t_dev = M * (profile.t_fwd(d, y, i, j) + profile.t_bwd(d, y, i, j))
            assert abs(sim.device_busy[d] - t_dev) <= \
                rel_tol * max(t_dev, 1e-12), (d, sim.device_busy[d], t_dev)
            assert sim.device_busy[d] <= sim.stage_busy[p] * (1 + rel_tol), \
                (d, p, sim.device_busy[d], sim.stage_busy[p])
    return sim


# ---------------------------------------------------------------------------
# Live replay: re-lowering and parameter migration
# ---------------------------------------------------------------------------


def relower(old: LoweredPlan, new_plan: Plan, cfg,
            model_axis: int | None = None) -> LoweredPlan:
    """Lower ``new_plan`` as a replacement for ``old`` on the same runtime.

    Beyond ``lower_plan``'s own checks, validates that the two lowered plans
    describe the same model and micro-batch structure, so the stacked period
    params (and optimizer state) can be migrated rather than re-initialized.
    """
    if old.arch and new_plan.arch and old.arch != new_plan.arch:
        raise LoweringError(f"arch changed across replay: {old.arch!r} -> "
                            f"{new_plan.arch!r}")
    new = lower_plan(new_plan, cfg, model_axis)
    if new.n_periods != old.n_periods:
        raise LoweringError(f"period count changed: {old.n_periods} -> "
                            f"{new.n_periods}")
    if new.global_batch != old.global_batch or new.n_micro != old.n_micro:
        raise LoweringError(
            f"batch structure changed: (B={old.global_batch}, M={old.n_micro})"
            f" -> (B={new.global_batch}, M={new.n_micro})")
    return new


def snap_plan(plan: Plan, lowered: LoweredPlan, L: int) -> Plan:
    """``plan`` with stage layer ranges set to what was deployed.

    Lowering snaps layer cuts to period boundaries and re-cuts them evenly
    (``even_periods``); the plan the runtime actually executes therefore
    owns the deployed ranges.  The returned plan (stage ranges and
    exec-step ranges rewritten; costs kept as the planner's estimates) is
    what a session should feed back into ``lightweight_replay`` so
    old-ownership accounting matches reality.
    """
    ranges = period_layers(lowered.stage_periods,
                           (L - 2) // lowered.n_periods, L)
    stages = tuple(dataclasses.replace(st, layers=r)
                   for st, r in zip(plan.stages, ranges))
    ex = iter(ranges)
    steps = tuple(dataclasses.replace(s, layers=next(ex))
                  if s.kind == "exec" else s for s in plan.steps)
    return dataclasses.replace(plan, stages=stages, steps=steps)


def period_owner(lp: LoweredPlan) -> tuple[int, ...]:
    """Owning stage of each canonical period under ``lp``'s split."""
    out = [0] * lp.n_periods
    for p, (i, j) in enumerate(lp.stage_periods):
        for t in range(i, j):
            out[t] = p
    return tuple(out)


def period_positions(lp: LoweredPlan) -> dict[int, int]:
    """canonical period -> row in ``lp``'s arranged period stack.

    The single source of truth for the ``runtime.pipeline.arrange_periods``
    layout (stage p's uniform slice ``[p*k, (p+1)*k)`` holds its assigned
    periods then zero padding) — migration, backup scatter/restore, and the
    bit-identicality checks all index through it.
    """
    pos: dict[int, int] = {}
    k = lp.k_per_stage
    for p, (i, j) in enumerate(lp.stage_periods):
        for t in range(i, j):
            pos[t] = p * k + (t - i)
    return pos


def migration_index(old: LoweredPlan, new: LoweredPlan):
    """Gather index mapping the OLD arranged period stack onto the NEW one.

    Returns ``(take, mask)`` such that
    ``new_leaf = where(mask, old_leaf[take], 0)``.
    """
    pos = period_positions(old)
    k_new = new.k_per_stage
    take: list[int] = []
    mask: list[float] = []
    for i, j in new.stage_periods:
        take += [pos[t] for t in range(i, j)] + [0] * (k_new - (j - i))
        mask += [1.0] * (j - i) + [0.0] * (k_new - (j - i))
    return take, mask


def _period_migrator(old: LoweredPlan, new: LoweredPlan):
    """leaf -> leaf gather realizing ``migration_index`` (pure jnp)."""
    import jax.numpy as jnp

    take, mask = migration_index(old, new)
    idx = jnp.asarray(take)
    m = jnp.asarray(mask, jnp.float32)

    def f(x):
        g = x[idx]
        keep = (m > 0).reshape(-1, *([1] * (g.ndim - 1)))
        return jnp.where(keep, g, jnp.zeros_like(g))

    return f


# ``old_owner`` sentinel for migrate_params: the period's old holder is not
# any stage of the new plan — it streams *directly* from an off-plan source
# (a draining/evicted leaver pushing its layers out, symmetric to a restore
# but from live state) instead of hopping adjacent-stage boundaries.
DIRECT_SOURCE = -1


@dataclasses.dataclass(frozen=True)
class MigrationReport:
    """What ``migrate_params`` moved, per boundary of the NEW plan."""

    moved_periods: tuple[int, ...]            # canonical indices that moved
    restored_periods: tuple[int, ...]         # restored from backup instead
    boundary_periods: tuple[tuple[int, ...], ...]   # per new-plan boundary
    boundary_bytes: tuple[float, ...]         # actual array bytes crossing
    period_bytes: float                       # bytes of one period's params
    total_bytes: float
    direct_periods: tuple[int, ...] = ()      # streamed off an off-plan source
    direct_bytes: float = 0.0


def migrate_params(params, old: LoweredPlan, new: LoweredPlan, *,
                   old_owner=None):
    """Pure migration of the stacked period params across a plan swap.

    The gather itself (``migration_index``) is direction-agnostic: it
    realizes any old->new stage re-arrangement, scale-in (a survivor
    absorbing a failed stage) and scale-out (periods landing on a freshly
    admitted device's stage) alike, bit-identically for every period that
    has an owner in both stacks.

    ``old_owner``: per-canonical-period owner in the NEW plan's stage
    coordinates; ``None`` entries mark periods restored from a backup and
    ``DIRECT_SOURCE`` entries periods streamed off an off-plan source (a
    draining leaver) — both excluded from boundary accounting.  Defaults to
    the old plan's own stage indices, which is exact when the stage count
    is unchanged.

    Returns ``(migrated_params, MigrationReport)``.  Leaves outside
    ``params["periods"]`` are returned untouched (vocab re-padding for a tp
    change is the session layer's job).
    """
    import jax

    f = _period_migrator(old, new)
    out = dict(params)
    out["periods"] = jax.tree.map(f, params["periods"])

    if old_owner is None:
        old_owner = period_owner(old)
    new_own = period_owner(new)
    moved = tuple(t for t in range(new.n_periods)
                  if old_owner[t] is not None
                  and old_owner[t] != DIRECT_SOURCE
                  and old_owner[t] != new_own[t])
    direct = tuple(t for t in range(new.n_periods)
                   if old_owner[t] == DIRECT_SOURCE)
    restored = tuple(t for t in range(new.n_periods) if old_owner[t] is None)
    period_bytes = sum(leaf.nbytes / leaf.shape[0]
                       for leaf in jax.tree.leaves(params["periods"]))
    boundary_periods: list[tuple[int, ...]] = []
    boundary_bytes: list[float] = []
    for p in range(new.stage - 1):
        crossing = tuple(t for t in moved
                         if min(old_owner[t], new_own[t]) <= p
                         < max(old_owner[t], new_own[t]))
        boundary_periods.append(crossing)
        boundary_bytes.append(period_bytes * len(crossing))
    report = MigrationReport(moved, restored, tuple(boundary_periods),
                             tuple(boundary_bytes), period_bytes,
                             period_bytes * len(moved)
                             + period_bytes * len(direct),
                             direct, period_bytes * len(direct))
    return out, report


def migrate_opt_state(opt_state, old: LoweredPlan, new: LoweredPlan):
    """Optimizer moments follow the params through the SAME index map."""
    import jax

    from repro.optim import AdamWState, SGDState

    f = _period_migrator(old, new)

    def mig(tree):
        out = dict(tree)
        out["periods"] = jax.tree.map(f, tree["periods"])
        return out

    if isinstance(opt_state, AdamWState):
        return AdamWState(opt_state.step, mig(opt_state.m), mig(opt_state.v))
    if isinstance(opt_state, SGDState):
        return SGDState(opt_state.step, mig(opt_state.mom))
    raise TypeError(type(opt_state))


def reconcile_migration(mig: MigrationReport, report, new: LoweredPlan,
                        table, pattern_len: int,
                        rel_tol: float = 1e-6) -> dict:
    """Assert ``migrate_params``'s moved bytes match the analytical
    ``RecoveryReport`` migration inputs (a replay run with
    ``layer_quantum=pattern_len`` so its cuts are period-aligned).

    Prices both directions: boundary crossings are checked per boundary of
    the new plan whether the periods flowed toward a survivor (scale-in) or
    onto a freshly admitted stage (scale-out) — the crossing predicate is
    symmetric in old/new owner.  Reports carrying ``direct_moves`` (a
    draining leaver streaming its layers straight to their new owners) are
    additionally reconciled against ``mig.direct_periods``.

    Returns per-boundary ``{analytic_bytes, table_bytes, runtime_bytes}``
    (plus a ``"direct"`` entry when direct streams were priced) where
    ``table_bytes`` re-prices the runtime's moved periods with the
    profiler's layer table — the quantity that must equal the analytical
    bytes exactly.
    """
    def period_table_bytes(periods):
        return sum(
            table.param_bytes(1 + t * pattern_len, 1 + (t + 1) * pattern_len)
            for t in periods)

    analytic = {bm.boundary: bm for bm in report.boundary_moves}
    out: dict = {}
    for p in range(new.stage - 1):
        periods = mig.boundary_periods[p]
        bm = analytic.get(p)
        if bm is None:
            assert not periods, (
                f"runtime moved periods {periods} across boundary {p} but "
                f"the recovery report shows no migration there")
            continue
        hull = set(range((bm.lo - 1) // pattern_len,
                         -(-(bm.hi - 1) // pattern_len)))
        assert set(periods) <= hull, (p, periods, sorted(hull))
        table_bytes = period_table_bytes(periods)
        assert abs(table_bytes - bm.nbytes) <= rel_tol * max(table_bytes, 1.0), (
            f"boundary {p}: runtime periods {periods} price to "
            f"{table_bytes:.0f} B in the layer table, but the recovery "
            f"report migrated {bm.nbytes:.0f} B")
        out[p] = {"analytic_bytes": bm.nbytes, "table_bytes": table_bytes,
                  "runtime_bytes": mig.boundary_bytes[p]}

    direct_moves = getattr(report, "direct_moves", ())
    if mig.direct_periods or direct_moves:
        hull = set()
        for dm in direct_moves:
            hull |= set(range((dm.lo - 1) // pattern_len,
                              -(-(dm.hi - 1) // pattern_len)))
        assert set(mig.direct_periods) <= hull, (
            f"runtime direct-streamed periods {mig.direct_periods} outside "
            f"the report's direct-move hull {sorted(hull)}")
        table_bytes = period_table_bytes(mig.direct_periods)
        # the analytic moves may also carry the leaver's embed/head bytes
        # (table edge pseudo-layers); compare on the real-layer span only
        L = table.L
        analytic_bytes = sum(
            table.param_bytes(max(dm.lo, 1), min(dm.hi, L - 1))
            for dm in direct_moves)
        assert abs(table_bytes - analytic_bytes) <= \
            rel_tol * max(table_bytes, 1.0), (
            f"direct streams: runtime periods {mig.direct_periods} price to "
            f"{table_bytes:.0f} B, but the report streams "
            f"{analytic_bytes:.0f} B of real layers off the leaver")
        out["direct"] = {"analytic_bytes": analytic_bytes,
                         "table_bytes": table_bytes,
                         "runtime_bytes": mig.direct_bytes}
    return out


# ---------------------------------------------------------------------------
# Runtime bridge
# ---------------------------------------------------------------------------


def plan_to_train_step(plan: Plan, profile: Profile | None, cfg,
                       production_mesh=None, *, check: bool = True, **kw):
    """Build a runnable distributed train step from an Asteroid ``Plan``.

    Returns ``(TrainStep, LoweredPlan)``.  ``production_mesh`` defaults to a
    ``(data=1, model=N)`` mesh over the local jax devices.  When ``profile``
    is given and ``check`` is True, the lowered schedule is cross-checked
    against the simulator before anything is compiled.
    """
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from repro.runtime.train import build_train_step_from_lowered

    if production_mesh is None:
        devs = jax.devices()
        production_mesh = Mesh(np.array(devs).reshape(1, len(devs)),
                               ("data", "model"))
    lowered = lower_plan(plan, cfg, production_mesh.shape["model"])
    if check and profile is not None:
        check_against_simulator(lowered, plan, profile)

    try:
        ts = build_train_step_from_lowered(cfg, production_mesh, lowered, **kw)
    except ValueError as e:
        raise LoweringError(str(e)) from e
    return ts, lowered
