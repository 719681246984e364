"""The training job: Asteroid's planned training step, timed on the chips.

Set-up makes the calls ``launch/train.py --plan`` makes, in its order: the
cluster's analytic profile, ``core.planner.plan_hpp``,
``core.lowering.plan_to_train_step``, the state on the mesh, and the jitted
``TrainStep.step_fn`` with params and optimizer state donated.  The
weights are the benchmark's own, drawn on the device from the seed and laid
out as the program lays out its own; the batches come from the traffic
generator, made on the host and placed by ``TrainStep.shard_batch`` one
step at a time, as the launcher does.

The first ``CHECKED_STEPS`` steps go through that same step and feed; they
warm up and give what ``correct`` compares: each step's loss, the first
gradient (read back from Adam's first moment after step 1) and the change
of the params after step 3, per leaf and layer.  Then the window
dispatches steps back to back for ``--seconds``, keeping at most
``IN_FLIGHT`` steps queued so that the host never runs ahead of the chip by
more than that; it ends when the last step dispatched has finished.

A traced run then maps the step's compiled text to program scopes
(``scopes.py``) and traces ``TRACED_STEPS`` more steps through the same
call, keeping their ``metrics`` output.  It stops, with no result, where
the text names no scope or the scopes cover under ``scopes.SCOPED_FLOOR``
of a chip's busy time.  The per-layer readers get the device time of each
scope, what the program counts about the built step
(``program_counters``), the mean of each step metric, the model's sizes and
its family's FLOP counts (``flops/``).

Once the window has closed, peak memory has been read and the program's
state is freed, the plain reference (``reference/``) trains the same
weights on the same batches for the same steps, a few sequences at a
time, at float32's highest matmul precision, on the same chips.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import math
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from benchmarks.chip import flops, scopes, trace
from benchmarks.chip.reference import common as ref
from benchmarks.chip.stream import BigramStream

CHECKED_STEPS = 3
IN_FLIGHT = 2
TRACED_STEPS = 3


def _replace(obj, values: dict):
    """A program config dataclass with ``values`` set, nested groups
    (``attn``, ``rwkv``) replaced field by field."""
    kw = {k: _replace(getattr(obj, k), v) if isinstance(v, dict) else v
          for k, v in values.items()}
    return dataclasses.replace(obj, **kw)


def program_config(config: dict, model: dict):
    """The program's ``ModelConfig`` with every size the config file
    states."""
    from repro.configs import get_config

    return _replace(get_config(config["arch"]), model)


@dataclasses.dataclass
class Readings:
    """What one run gives for ``correct``: per-step losses, first-gradient
    and param-change norms per leaf (and layer)."""

    losses: list
    grad: dict
    change: dict


class Program:
    """The system under test, built once; ``checked_steps`` and ``drive``
    train one seed's weights through it."""

    def __init__(self, cell, devs, log):
        from repro.core.hardware import env_v5e
        from repro.core.lowering import plan_to_train_step
        from repro.core.planner import plan_hpp
        from repro.core.profiler import LayerTable, Profile
        from repro.distributed.sharding import named
        from repro.optim import AdamW, AdamWState, cosine_schedule

        t = self.traffic = cell.traffic
        self.model = cell.config["model"]
        cfg = self.cfg = program_config(cell.config, self.model)
        n = len(devs)
        # every chip a pipeline stage or a share of one: no data replicas
        mesh = Mesh(np.array(devs).reshape(1, n), ("data", "model"))
        o = t["optimizer"]
        self.opt = AdamW(lr=cosine_schedule(o["lr"], warmup=o["warmup"],
                                            total=o["total"],
                                            floor=o["floor"]),
                         b1=o["b1"], b2=o["b2"], eps=o["eps"],
                         weight_decay=o["weight_decay"],
                         grad_clip=o["grad_clip"])
        gb, seq = t["global_batch"], t["seq"]
        table = LayerTable.from_model_config(cfg, seq)
        prof = Profile.analytic(table, env_v5e(n).sorted_by_memory(),
                                max_batch=gb)
        n_periods = cfg.n_layers // len(cfg.pattern)
        divisors = {d for d in range(1, n + 1)
                    if n % d == 0 and d <= n_periods}
        self.plan = plan_hpp(prof, gb, gb // t["n_micro"], arch=cfg.name,
                             allowed_stages=divisors, intra_opt="auto",
                             staleness=0, compress=None)
        self.ts, lowered = plan_to_train_step(
            self.plan, prof, cfg, mesh, optimizer=self.opt, staleness=0,
            double_buffer=None, compress="none", quant_tile=256,
            bucket_mb=None, error_feedback=True)
        log(f"plan: {lowered.stage} stages, periods {lowered.stage_periods}, "
            f"M={lowered.n_micro}, K_p={lowered.warmup}, predicted round "
            f"latency {self.plan.latency:.6f} s")
        if self.ts.spec.bucketed or self.ts.spec.staleness:
            raise SystemExit("the train job drives the synchronous, "
                             "unbucketed step only")

        spec = self.ts.spec
        self.layer_rows = _layer_rows(spec.stage_periods, n_periods)
        self.family = ref.family(cell.config["reference"])
        shardings = named(self.ts.mesh, self.ts.param_specs)
        _check_tree(cfg, self.family, self.model)

        def make(key):
            return _to_program(self.family.init(ref.Draw(key), self.model),
                               cfg, spec)

        self._make = jax.jit(make, out_shardings=shardings)
        rep = NamedSharding(self.ts.mesh, P())
        self._opt_init = jax.jit(self.opt.init, out_shardings=AdamWState(
            rep, shardings, shardings))
        self._norms = jax.jit(ref.leaf_norms)
        # the start is drawn again inside the norm, not kept beside the state
        self._change = jax.jit(lambda params, key: ref.leaf_norms(
            jax.tree.map(jnp.subtract, params, make(key))))
        # as the launcher: the loop rebinds params and optimizer state, so
        # the step writes its outputs over their buffers
        self.step_fn = jax.jit(self.ts.step_fn, donate_argnums=(0, 1))

    def tokens_per_step(self) -> int:
        return self.traffic["global_batch"] * self.traffic["seq"]

    def stream(self, seed: int) -> BigramStream:
        return BigramStream(self.cfg.vocab_size, self.traffic["seq"], seed)

    def checked_steps(self, seed: int, ds: BigramStream):
        """Init from the seed and run the checked steps.  Returns the state
        to train on and the readings."""
        key = ref.seed_key(seed)
        params = self._make(key)
        opt_state = self._opt_init(params)
        losses, grad = [], None
        for step in range(CHECKED_STEPS):
            batch = self.ts.shard_batch(ds.batch(step, self.traffic[
                "global_batch"]))
            params, opt_state, loss, _ = self.step_fn(params, opt_state,
                                                      batch)
            losses.append(loss)
            if step == 0:
                # Adam's first moment after one step is (1 - b1) * g
                grad = ref.named_rows(self._norms(opt_state.m),
                                      self.layer_rows)
                grad = {k: v / (1 - self.opt.b1) for k, v in grad.items()}
        change = ref.named_rows(self._change(params, key), self.layer_rows)
        return (params, opt_state), Readings([float(x) for x in losses],
                                             grad, change)

    def drive(self, state, ds, first_step: int, stop, span=None):
        """Dispatch steps from ``first_step`` until ``stop(n_done)`` holds
        after a dispatch, at most ``IN_FLIGHT`` queued; then wait for the
        last.  A host span named ``span`` opens once the first step is
        dispatched (the chip is busy from then on) and closes after the
        wait; under it the steps' ``metrics`` outputs are kept.  Returns
        ``(state, steps, losses, metrics)``."""
        params, opt_state = state
        gb = self.traffic["global_batch"]
        queue, losses, step = collections.deque(), [], first_step
        kept = []
        with contextlib.ExitStack() as window:
            while True:
                with TraceAnnotation("bench.make_batch"):
                    batch = self.ts.shard_batch(ds.batch(step, gb))
                with TraceAnnotation(trace.DISPATCH_SPAN):
                    params, opt_state, loss, metrics = self.step_fn(
                        params, opt_state, batch)
                if span and step == first_step:
                    window.enter_context(TraceAnnotation(span))
                if span:
                    kept.append(metrics)
                losses.append(loss)
                queue.append(loss)
                step += 1
                if len(queue) > IN_FLIGHT:
                    with TraceAnnotation("bench.wait_queue"):
                        queue.popleft().block_until_ready()
                if stop(step - first_step):
                    break
            with TraceAnnotation("bench.wait_last"):
                jax.block_until_ready((params, opt_state))
        return (params, opt_state), step - first_step, losses, kept


def program_counters(spec) -> dict:
    """What the program counts about the built step: its period slots,
    real and computed (``pipeline.slot_counts``)."""
    from repro.runtime.pipeline import slot_counts

    real, computed = slot_counts(spec)
    return {"slots_real": real, "slots_computed": computed}


def mean_metrics(kept: list) -> dict:
    """The mean over steps of each scalar of the steps' ``metrics``."""
    kept = jax.device_get(kept)
    return {k: float(np.mean([m[k] for m in kept])) for k in kept[0]
            if np.ndim(kept[0][k]) == 0} if kept else {}


def _layer_rows(stage_periods, n_periods: int) -> list:
    """Row of the program's period stack that holds each layer, in order."""
    if stage_periods is None:
        return list(range(n_periods))
    k = max(j - i for i, j in stage_periods)
    return [p * k + s for p, (i, j) in enumerate(stage_periods)
            for s in range(j - i)]


def _to_program(params, cfg, spec):
    """The program's layout of a reference-layout tree: the period stack
    arranged for the stage split, the vocabulary padded for the mesh."""
    from repro.runtime.pipeline import arrange_periods, pad_periods
    from repro.runtime.train import pad_vocab_params

    if spec.stage_periods is not None:
        periods, _ = arrange_periods(params["periods"], spec.stage_periods)
    else:
        periods, _ = pad_periods(params["periods"], cfg.n_periods,
                                 spec.plan.stage)
    return pad_vocab_params({**params, "periods": periods}, cfg, spec.plan.tp)


def _check_tree(cfg, family, model: dict) -> None:
    """The weights drawn here must have the program's own leaves."""
    from repro.models.model import init_model

    def sig(tree):
        return jax.tree_util.tree_map_with_path(
            lambda p, x: (jax.tree_util.keystr(p), x.shape, str(x.dtype)),
            tree)

    key = jax.random.PRNGKey(0)
    ours = jax.tree.leaves(sig(jax.eval_shape(
        lambda: family.init(ref.Draw(key), model))), is_leaf=_is_sig)
    theirs = jax.tree.leaves(sig(jax.eval_shape(
        lambda: init_model(key, cfg))), is_leaf=_is_sig)
    if ours != theirs:
        diff = sorted(set(ours) ^ set(theirs))[:6]
        raise SystemExit(f"the reference's leaves differ from the "
                         f"program's: {diff}")


def _is_sig(x) -> bool:
    return isinstance(x, tuple) and len(x) == 3 and isinstance(x[0], str)


# ---------------------------------------------------------------------------
# Reference
# ---------------------------------------------------------------------------


def _spread_over(abstract, devs):
    """Each leaf split over ``devs`` along its last axis past the first
    that divides evenly, else replicated (XLA partitions the reference)."""
    mesh = Mesh(np.array(devs), ("x",))

    def one(leaf):
        for ax in range(leaf.ndim - 1, 0, -1):
            if leaf.shape[ax] % len(devs) == 0:
                return NamedSharding(mesh, P(*[None] * ax, "x"))
        return NamedSharding(mesh, P())

    return jax.tree.map(one, abstract)


class Reference:
    """The plain reference's readings for the checked steps, built once.
    ``precision`` is that of the model's matmul operands (``common.operand``):
    "int8" makes it the control, "bfloat16" the precision the configuration
    states."""

    def __init__(self, cell, devs, precision: str = "float32"):
        self.model = cell.config["model"]
        self.opt = cell.traffic["optimizer"]
        self.family = ref.family(cell.config["reference"])
        model, fam = self.model, self.family
        abstract = jax.eval_shape(
            lambda: fam.init(ref.Draw(jax.random.PRNGKey(0)), model))
        sh = _spread_over(abstract, devs)
        self._init = jax.jit(lambda k: fam.init(ref.Draw(k), model),
                             out_shardings=sh)
        self._zeros = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p),
                              out_shardings=sh)

        def step(params, m, v, tokens, n):
            loss, grads = ref.blocked_loss_and_grad(
                lambda p, t: fam.loss(p, t, model, precision), params, tokens)
            params, m, v, grads = ref.adamw(params, m, v, grads, n, self.opt)
            return params, m, v, loss, ref.leaf_norms(grads)

        self._step = jax.jit(step, donate_argnums=(0, 1, 2),
                             out_shardings=(sh, sh, sh, None, None))
        self._change = jax.jit(lambda params, key: ref.leaf_norms(
            jax.tree.map(jnp.subtract, params, fam.init(ref.Draw(key),
                                                        model))))

    def readings(self, seed: int, batches: list) -> Readings:
        with jax.default_matmul_precision("highest"):
            key = ref.seed_key(seed)
            params = self._init(key)
            m, v = self._zeros(params), self._zeros(params)
            losses, grad = [], None
            for n, b in enumerate(batches, start=1):
                params, m, v, loss, g = self._step(
                    params, m, v, jnp.asarray(b["tokens"]), jnp.int32(n))
                losses.append(float(loss))
                grad = grad or ref.named_rows(g)
            del m, v
            change = ref.named_rows(self._change(params, key))
        return Readings(losses, grad, change)


def compare(prog: Readings, want: Readings) -> dict:
    """Every number ``correct`` can hold to a limit, with where each is
    worst; a cell's ``limits`` file names those it holds."""
    keep = ref.moved_rows(want.grad)
    gaps = [abs(a - b) for a, b in zip(prog.losses, want.losses)]
    if not all(math.isfinite(x) for x in prog.losses):
        gaps = [math.inf] * len(gaps)
    out = {"loss_gap": (max(gaps), "steps 1-3"),
           "loss1_gap": (gaps[0], "step 1")}
    for name, p, w in (("grad", prog.grad, want.grad),
                       ("change", prog.change, want.change)):
        out[f"{name}_gap"] = ref.worst_gap(p, w, keep)
        out[f"{name}_gap_median"] = (ref.median_gap(p, w, keep), "all rows")
    return out


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------


def run(cell, devs, log, seed: int, seconds: float, traced: bool,
        t_start: float) -> dict:
    """One run of a training cell; returns what the harness prints."""
    prog = Program(cell, devs, log)
    ds = prog.stream(seed)
    state, readings = prog.checked_steps(seed, ds)
    tokens = prog.tokens_per_step()

    t0 = time.perf_counter()
    setup_s = t0 - t_start
    deadline = t0 + seconds
    state, n_steps, window_losses, _ = prog.drive(
        state, ds, CHECKED_STEPS, lambda n: time.perf_counter() >= deadline)
    window_s = time.perf_counter() - t0
    tok_s = n_steps * tokens / window_s
    log(f"window: {n_steps} steps of {tokens} tokens in {window_s:.6f} s, "
        f"{tok_s:.3f} tokens/s; set-up {setup_s:.6f} s")

    gb, seq = cell.traffic["global_batch"], cell.traffic["seq"]
    summary, step_metrics = None, {}
    if traced:
        t_map = time.perf_counter()
        smap = scopes.scope_map(scopes.compiled_text(
            prog.step_fn, *state, prog.ts.shard_batch(ds.batch(0, gb))))
        named = sorted({v for v in smap.scopes.values() if v})
        log(f"scope map: module {smap.module}, scopes {named}, "
            f"{time.perf_counter() - t_map:.3f} s")
        if not named:
            raise SystemExit("the step's compiled text names no program "
                             "scope: no per-layer metric could be read")

        def traced_window():
            return prog.drive(state, ds, CHECKED_STEPS + n_steps,
                              lambda n: n >= TRACED_STEPS,
                              span=trace.WINDOW_SPAN)

        (state, _, more, kept), summary = trace.traced(traced_window, smap)
        window_losses += more
        step_metrics = mean_metrics(kept)
        shares = summary.scoped_shares()
        log(f"trace: {TRACED_STEPS} steps, window "
            f"{summary.window_ns / 1e9:.6f} s, busy {summary.busy_s:.6f} s "
            f"per chip, scoped " + " ".join(f"{100 * x:.2f}%" for x in shares)
            + f" of busy; step metrics {step_metrics}")
        if min(shares) < scopes.SCOPED_FLOOR:
            raise SystemExit(
                f"the program scopes cover {100 * min(shares):.2f}% of a "
                f"chip's busy time, under {100 * scopes.SCOPED_FLOOR:.0f}%: "
                f"the map and the trace do not match")

    window_losses = [float(x) for x in window_losses]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                for d in devs)
    log(f"peak_bytes_in_use per chip: " + " ".join(
        f"{d.id}:{(d.memory_stats() or {}).get('peak_bytes_in_use')}"
        for d in devs))
    attempted = CHECKED_STEPS + len(window_losses)
    failed = sum(not math.isfinite(x)
                 for x in readings.losses + window_losses)
    plan_latency = prog.plan.latency
    counters = program_counters(prog.ts.spec)
    family, model = cell.config["reference"], cell.config["model"]
    del state, prog

    t_ref = time.perf_counter()
    want = Reference(cell, devs).readings(
        seed, [ds.batch(s, gb) for s in range(CHECKED_STEPS)])
    log(f"reference: {time.perf_counter() - t_ref:.3f} s; losses: program "
        f"{readings.losses} reference {want.losses}")
    return {
        "end_to_end": {"train_tok_s": tok_s, "setup_s": setup_s},
        "attempted": attempted, "failed": failed,
        "checks": {k: v for k, v in compare(readings, want).items()
                   if k in cell.limits},
        "memory_peak_bytes": peak,
        "trace": summary,
        "layer_inputs": {
            "tok_s": tok_s, "chips": len(devs),
            "step_s": window_s / n_steps, "plan_latency_s": plan_latency,
            "traced_steps": TRACED_STEPS if traced else 0,
            "flops_per_token": flops.train_flops_per_token(family, model,
                                                           seq),
            "scope_s": summary.scope_s(TRACED_STEPS) if summary else {},
            "counters": counters, "step_metrics": step_metrics,
            "model": model, "family": family, "seq": seq,
            "global_batch": gb,
            "scope_work": flops.scope_work(family, model, seq),
        },
    }
