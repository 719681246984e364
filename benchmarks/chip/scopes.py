"""Device time of a training cell by program scope.

The train step wraps its layers in ``jax.named_scope("asteroid/<name>")``
(``repro.scopes``).  The names land in the compiled HLO's
``op_name`` metadata, under the ``jvp``, ``transpose`` and ``checkpoint``
wrappers of autodiff and remat; the last ``asteroid/`` component of an
instruction's ``op_name`` is its innermost scope.  The instruction names of
the compiled text are those the chip's trace prints, so ``scope_map`` puts
each device op of a trace down to a program layer.

``reduce`` reads a ``.xplane.pb`` as ``trace.reduce`` does (the same window,
the same clock shift and the same leaf ops) and gives, for each chip:

* the device time of each scope: the union of the intervals of the leaf ops
  whose innermost scope it is, ops of the step's module only;
* the time of the ops with no scope, with the largest of them by name;
* each idle gap with the scope of the op that ends it and the innermost
  host span (``bench.*`` or ``asteroid.*``) over its middle;
* the clock check: for each run of the step's module, how far the host's
  ``CompleteCallbacks`` event of the same ``run_id`` starts after the
  module's end, once the device clock is shifted as ``trace.reduce``
  shifts it.

Run as a script it measures one cell's step, traced, and prints and writes
the breakdown:

    python3 benchmarks/chip/scopes.py --workload <name> --seed <n> \
        --out DIR [--rehearse] [--keep DIR]

It runs the cell's training job as ``run.py`` does (set-up and the checked
steps), then the job's ``TRACED_STEPS`` steps untraced and as many traced,
and compiles the step's text from abstract shapes with the shardings and
donation the step ran with.  ``--keep DIR`` keeps the trace and the gzipped
HLO text there.  Without as many TPU chips as the cell asks for it exits
non-zero.  ``--rehearse`` runs the smoke sizes the cell's files give on
whatever JAX finds: on a TPU it traces them (``--rehearse --keep`` records
``tests/data``'s fixture), elsewhere it stops before the trace; either way
it exits non-zero, as it does when the step's text names no scope (a
persistent-cache entry compiled without them has the same key).
"""

from __future__ import annotations

import bisect
import dataclasses
import gzip
import json
import re
import shutil
import sys
import tempfile
import time
from pathlib import Path

if __name__ == "__main__":
    ROOT = Path(__file__).resolve().parents[2]
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from benchmarks.chip import trace  # noqa: E402

SCOPE = re.compile(r"asteroid/(\w+)")
HOST_SPANS = ("bench.", "asteroid.")
CALLBACK = "CompleteCallbacks"
# one instruction of HLO text: its name, then (on the same line) metadata
INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?"
                         r"metadata=\{[^}]*?op_name=\"([^\"]*)\"")
MODULE = re.compile(r"^HloModule ([\w.\-]+)")


def innermost(op_path: str) -> str | None:
    """The last ``asteroid/<name>`` of an ``op_name`` path, or None."""
    found = SCOPE.findall(op_path)
    return found[-1] if found else None


@dataclasses.dataclass
class ScopeMap:
    module: str            # the HLO module's name (``jit_step_fn``)
    scopes: dict           # instruction name -> innermost scope or None


def scope_map(hlo_text: str) -> ScopeMap:
    """Instruction name to innermost scope, from compiled HLO text."""
    m = MODULE.search(hlo_text)
    if m is None:
        raise ValueError("no HloModule line in the HLO text")
    scopes = {}
    for line in hlo_text.splitlines():
        hit = INSTRUCTION.match(line)
        if hit:
            scopes[hit.group(1)] = innermost(hit.group(2))
    return ScopeMap(m.group(1), scopes)


def compiled_text(jitted, *args) -> str:
    """The compiled HLO text of ``jitted`` for arguments shaped, laid out
    and donated like ``args`` (which may already be donated: only their
    shapes, dtypes and shardings are read)."""
    import jax

    def abstract(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)

    return jitted.lower(*jax.tree.map(abstract, args)).compile().as_text()


@dataclasses.dataclass
class ChipScopes:
    name: str
    busy_ns: float
    scope_ns: dict          # scope -> union of its ops' intervals
    scoped_ns: float        # union over every scoped op
    unscoped_ns: float      # union over the ops with no scope
    unscoped_op_ns: dict    # op name -> summed time, ops with no scope
    matched_ns: float       # step-module op time whose name the map knows
                            # (ops with op_name metadata)
    step_ns: float          # step-module op time
    gaps: list              # (start, end, scope, name) of the op after
    clock_residual_ns: list  # per run of the step's module

    def scope_gaps(self) -> dict:
        """Idle time before the ops of each scope (None: no scope or no
        next op)."""
        out: dict = {}
        for s, e, sc, _ in self.gaps:
            out[sc] = out.get(sc, 0.0) + (e - s)
        return out


def chip_scopes(name: str, events, modules, window, smap: ScopeMap,
                callbacks=()) -> ChipScopes:
    """One chip's scope times from its ``(start, end, op name)`` events and
    the ``(start, end)`` runs of the step's module, on the host's clock,
    over ``window``.  ``callbacks`` pairs each module run's end with its
    ``CompleteCallbacks`` start, both on the host's clock."""
    base = trace.chip_summary(name, events, window)
    w0, w1 = window
    runs = trace.union(modules)
    run_starts = [s for s, _ in runs]
    by_scope: dict = {}
    unscoped, scoped, op_ns = [], [], {}
    matched = step = 0.0
    first_at: dict = {}
    for s, e, op in sorted(trace.leaves(events)):
        s, e = max(s, w0), min(e, w1)
        if e <= s:
            continue
        i = bisect.bisect_right(run_starts, s) - 1
        in_step = i >= 0 and s < runs[i][1]
        sc = smap.scopes.get(op) if in_step else None
        if in_step:
            step += e - s
            matched += (e - s) if op in smap.scopes else 0.0
        if sc:
            by_scope.setdefault(sc, []).append((s, e))
            scoped.append((s, e))
        else:
            unscoped.append((s, e))
            op_ns[op] = op_ns.get(op, 0.0) + (e - s)
        first_at.setdefault(s, (sc, op))
    return ChipScopes(
        name, base.busy_ns,
        {k: trace.total(trace.union(v)) for k, v in by_scope.items()},
        trace.total(trace.union(scoped)), trace.total(trace.union(unscoped)),
        op_ns, matched, step,
        [(s, e, *first_at.get(e, (None, None))) for s, e in base.gaps],
        [cb - end for end, cb in callbacks])


@dataclasses.dataclass
class Breakdown:
    window_ns: float
    chips: list
    host_spans: list        # (start, end, name) of bench.* and asteroid.*

    def host_activity(self, t: float) -> str:
        return trace.Summary(self.window_ns, [], self.host_spans) \
            .host_activity(t)


def reduce(path: str, smap: ScopeMap) -> Breakdown:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    spans, callbacks = [], {}
    for plane in pd.planes:
        if plane.name.startswith(trace.DEVICE_PLANE):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(HOST_SPANS):
                    spans.append((ev.start_ns, ev.end_ns, ev.name))
                elif ev.name == CALLBACK:
                    st = dict(ev.stats)
                    callbacks[(st.get("device_ordinal"), st.get("run_id"))] \
                        = ev.start_ns
    # the window and the shift of trace.reduce
    (w0, w1), = [(s, e) for s, e, n in spans if n == trace.WINDOW_SPAN]
    dispatched = min((e for s, e, n in spans if n == trace.DISPATCH_SPAN),
                     default=w0)

    chips = []
    for plane in sorted(pd.planes, key=lambda p: p.name):
        if not plane.name.startswith(trace.DEVICE_PLANE):
            continue
        ordinal = int(plane.name.rsplit(":", 1)[1])
        lines = {line.name: list(line.events) for line in plane.lines}
        mods = lines.get(trace.MODULES_LINE, [])
        first = min((ev.start_ns for ev in mods), default=dispatched)
        shift = dispatched - first
        events = [(ev.start_ns + shift, ev.end_ns + shift,
                   trace.op_name(ev.name))
                  for ev in lines.get(trace.OPS_LINE, [])]
        step_runs, cbs = [], []
        for ev in mods:
            if ev.name.split("(", 1)[0] != smap.module:
                continue
            s, e = ev.start_ns + shift, ev.end_ns + shift
            step_runs.append((s, e))
            cb = callbacks.get((ordinal, dict(ev.stats).get("run_id")))
            if cb is not None:
                cbs.append((e, cb))
        chips.append(chip_scopes(plane.name, events, step_runs, (w0, w1),
                                 smap, cbs))
    return Breakdown(float(w1 - w0), chips,
                     [sp for sp in spans if sp[2] != trace.WINDOW_SPAN])


def per_step(chip: ChipScopes, steps: int) -> dict:
    """What one chip's line and the written breakdown give, ms per step."""
    ms = 1e6 * steps
    top = sorted(chip.unscoped_op_ns.items(), key=lambda kv: -kv[1])[:5]
    gaps = chip.scope_gaps()
    res = sorted(chip.clock_residual_ns)
    return {
        "scope_ms": {k: v / ms for k, v in sorted(chip.scope_ns.items())},
        "unscoped_ms": chip.unscoped_ns / ms,
        "busy_ms": chip.busy_ns / ms,
        "scoped_pct": 100.0 * chip.scoped_ns / chip.busy_ns,
        "sum_over_busy_pct": 100.0 * (sum(chip.scope_ns.values())
                                      + chip.unscoped_ns) / chip.busy_ns,
        "matched_pct": 100.0 * chip.matched_ns / max(chip.step_ns, 1.0),
        "idle_before_ms": {str(k): v / ms for k, v in gaps.items()},
        "top_unscoped_ms": [[k, v / ms] for k, v in top],
        "clock_residual_ms": [r / 1e6 for r in res],
    }


# ---------------------------------------------------------------------------
# One measured run
# ---------------------------------------------------------------------------


def measure(cell, devs, log, seed: int, keep: Path | None):
    """One run: returns what ``main`` writes.  Off a TPU it stops before
    the trace (the profiler records no device plane there)."""
    from benchmarks.chip.jobs import train
    from repro.runtime.pipeline import slot_counts

    steps = train.TRACED_STEPS

    prog = train.Program(cell, devs, log)
    ds = prog.stream(seed)
    state, _ = prog.checked_steps(seed, ds)
    first = train.CHECKED_STEPS
    tokens = prog.tokens_per_step()

    t0 = time.perf_counter()
    state, _, _ = prog.drive(state, ds, first, lambda n: n >= steps)
    plain_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    batch = prog.ts.shard_batch(ds.batch(0, prog.traffic["global_batch"]))
    text = compiled_text(prog.step_fn, *state, batch)
    smap = scope_map(text)
    compile_s = time.perf_counter() - t0
    if keep:
        with gzip.open(keep / f"{cell.name}.hlo.txt.gz", "wt") as f:
            f.write(text)

    real, computed = slot_counts(prog.ts.spec)
    out = {
        "workload": cell.name, "steps": steps, "seed": seed,
        "device": {"platform": devs[0].platform,
                   "kind": devs[0].device_kind, "count": len(devs)},
        "module": smap.module,
        "scopes_in_text": sorted({v for v in smap.scopes.values() if v}),
        "slots": [real, computed], "slot_use_pct": 100.0 * real / computed,
        "tok_s_untraced": steps * tokens / plain_s,
        "compile_text_s": compile_s,
    }
    log(f"slots {real}/{computed} ({out['slot_use_pct']:.2f}%); step text "
        f"compiled in {compile_s:.3f} s, module {smap.module}, scopes "
        f"{out['scopes_in_text']}")
    if devs[0].platform != "tpu":
        return out

    timed = {}

    def traced_window():
        t = time.perf_counter()
        done = prog.drive(state, ds, first + steps, lambda n: n >= steps,
                          span=trace.WINDOW_SPAN)
        timed["s"] = time.perf_counter() - t
        return done

    tmp = Path(tempfile.mkdtemp(prefix="chipbench-scopes-"))
    try:
        path = str((keep or tmp) / f"{cell.name}.xplane.pb")
        _, summary = trace.traced(traced_window, keep_as=path)
        br = reduce(path, smap)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    out.update({
        "tok_s_traced": steps * tokens / timed["s"],
        "busy_s": summary.busy_s, "window_s": summary.window_ns / 1e9,
        "chips": {c.name: per_step(c, steps) for c in br.chips},
        "longest_gaps": sorted(
            ([(e - s) / 1e6, str(sc), op, br.host_activity((s + e) / 2)]
             for c in br.chips for s, e, sc, op in c.gaps),
            reverse=True)[:12],
    })
    log(f"tokens/s {out['tok_s_untraced']:.1f} untraced, "
        f"{out['tok_s_traced']:.1f} traced")
    for name, c in out["chips"].items():
        log(f"scopes {name}: " + " ".join(
            f"{k} {v:.3f}" for k, v in c["scope_ms"].items())
            + f" ms/step; unscoped {c['unscoped_ms']:.3f} ms/step; scoped "
            f"{c['scoped_pct']:.2f}% of busy (sum {c['sum_over_busy_pct']:.2f}"
            f"%, map matched {c['matched_pct']:.2f}%); idle before: " + " ".join(
                f"{k} {v:.3f}" for k, v in c["idle_before_ms"].items())
            + " ms/step")
    return out


def main(argv=None) -> None:
    import argparse

    from benchmarks.chip import harness

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rehearse", action="store_true",
                    help="smoke sizes on any platform; exits non-zero")
    ap.add_argument("--keep", type=Path,
                    help="directory to keep the trace and HLO text in")
    ap.add_argument("--out", type=Path, required=True,
                    help="directory to write the breakdown's JSON to")
    args = ap.parse_args(argv)

    cell = harness.Cell.load(args.workload)
    if args.rehearse:
        cell = cell.rehearsal()
    devs = harness.take_devices(cell.chips, require_tpu=not args.rehearse)
    log = harness.Log(devs)
    if args.keep:
        args.keep.mkdir(parents=True, exist_ok=True)
    out = measure(cell, devs, log, args.seed, args.keep)
    args.out.mkdir(parents=True, exist_ok=True)
    tag = "smoke" if args.rehearse else "full"
    with open(args.out / f"{cell.name}.{tag}.{args.seed}.json", "w") as f:
        json.dump(out, f, indent=1)
    if not out["scopes_in_text"]:
        raise SystemExit("the step's compiled text names no scope")
    if args.rehearse or devs[0].platform != "tpu":
        raise SystemExit("rehearsal: not a chip run")


if __name__ == "__main__":
    main()
