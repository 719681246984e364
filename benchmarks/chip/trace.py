"""From a profiler trace to the numbers the per-layer metrics read.

``traced`` records a ``jax.profiler`` trace of a few steps.  ``reduce``
reads its ``.xplane.pb`` with ``jax.profiler.ProfileData`` and, for each
chip, over the traced window (the host span ``bench.trace_window``):

* busy time: the union of the intervals of the chip's leaf XLA ops.  Only
  leaf ops count, here and below: a ``while`` op's event spans the ops of
  its body and the gaps between them;
* exposed collective time: the part of the collective ops' intervals in
  which no other op of that chip runs;
* op totals by name, and the idle gaps between busy intervals, each named
  by the innermost host span that covers its middle: the benchmark's
  ``bench.*`` or the program's ``asteroid.*`` (``repro.scopes``);
* given the step's scope map (``scopes.scope_map``), the device time of
  each program scope: the union of the intervals of the leaf ops of the
  step's module whose innermost ``asteroid/*`` scope it is.  A scope with
  children (``pipeline``, ``stage``) so gets its self time.

The device's clock in the trace can sit a millisecond or more off the
host's.  Each chip's events are shifted so that its first program starts
when the first ``bench.dispatch`` span ends: the chips are idle before it,
so the first step runs as soon as it is dispatched.  The window opens
then, and closes when the host has waited for the last step.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import shutil
import tempfile

WINDOW_SPAN = "bench.trace_window"
DISPATCH_SPAN = "bench.dispatch"
HOST_SPANS = ("bench.", "asteroid.")
DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "send", "recv")


def traced(fn, smap=None):
    """Run ``fn()`` under the profiler.  Returns ``(fn(), Summary)``, with
    the time by scope of ``smap`` (a ``scopes.ScopeMap``) where given; the
    trace file is deleted after reading."""
    import jax

    tmp = tempfile.mkdtemp(prefix="chipbench-trace-")
    try:
        jax.profiler.start_trace(tmp)
        try:
            out = fn()
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                            recursive=True)
        return out, reduce(path, smap)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def op_name(event_name: str) -> str:
    """An XLA op event is named by its HLO text (``%fusion.3 = f32[..]
    fusion(..), kind=kOutput``); the instruction's name alone is kept."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def is_collective(name: str) -> bool:
    """Whether an instruction name is a collective's (XLA names each
    instruction after its opcode: ``all-reduce.1``,
    ``collective-permute-done.2``)."""
    n = name.lower()
    return any(n.startswith(c) for c in COLLECTIVES)


def union(intervals):
    """Merge ``(start, end)`` pairs into sorted disjoint intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def leaves(events):
    """The ``(start, end, name)`` events that contain no other event."""
    out, stack = [], []          # stack entries: [start, end, name, parent]
    for ev in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][1] <= ev[0]:
            top = stack.pop()
            if not top[3]:
                out.append(tuple(top[:3]))
        if stack and ev[1] <= stack[-1][1]:
            stack[-1][3] = True
        stack.append([*ev, False])
    out += [tuple(top[:3]) for top in stack if not top[3]]
    return out


def total(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def subtract(a, b):
    """Parts of the disjoint sorted intervals ``a`` not covered by ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


@dataclasses.dataclass
class Chip:
    name: str
    busy_ns: float
    exposed_collective_ns: float
    op_ns: dict                   # op name -> summed duration in the window
    gaps: list                    # (start, end) idle intervals in the window
    # program scope -> union of its ops' intervals in the window
    scope_ns: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Summary:
    window_ns: float
    chips: list
    host_spans: list              # (start, end, name) of HOST_SPANS

    @property
    def busy_s(self) -> float:
        """Busy seconds, averaged over the chips."""
        return sum(c.busy_ns for c in self.chips) / len(self.chips) / 1e9

    def idle_share(self) -> float:
        """Largest idle share of the window over the chips."""
        return max(1.0 - c.busy_ns / self.window_ns for c in self.chips)

    def scoped_shares(self) -> list:
        """Per chip, the device time of its scopes over its busy time."""
        return [sum(c.scope_ns.values()) / max(c.busy_ns, 1.0)
                for c in self.chips]

    def scope_s(self, steps: int) -> dict:
        """Seconds per step of each program scope, one entry per chip."""
        names = sorted({k for c in self.chips for k in c.scope_ns})
        return {k: [c.scope_ns.get(k, 0.0) / 1e9 / steps for c in self.chips]
                for k in names}

    def top_ops(self, n: int = 10) -> list:
        tot: dict = {}
        for c in self.chips:
            for k, v in c.op_ns.items():
                tot[k] = tot.get(k, 0.0) + v
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v / len(self.chips) / 1e9] for k, v in top]

    def longest_gaps(self, n: int = 10) -> list:
        gaps = sorted(((e - s, s, e) for c in self.chips for s, e in c.gaps),
                      reverse=True)[:n]
        return [[self.host_activity((s + e) / 2), d / 1e9]
                for d, s, e in gaps]

    def host_activity(self, t: float) -> str:
        """The innermost host span that covers time ``t``."""
        best = None
        for s, e, name in self.host_spans:
            if s <= t <= e and (best is None or e - s < best[1] - best[0]):
                best = (s, e, name)
        return best[2] if best else "outside any bench span"


def chip_summary(name: str, events, window, smap=None, runs=()) -> Chip:
    """One chip's numbers from its ``(start, end, op name)`` events, on the
    host's clock, over ``window = (start, end)``; with the step's scope map
    and the ``(start, end)`` runs of its module, its time by scope."""
    w0, w1 = window
    runs = union(runs)
    run_starts = [s for s, _ in runs]
    ops, comm, op_ns, by_scope = [], [], {}, {}
    for s, e, op in leaves(events):
        s, e = max(s, w0), min(e, w1)
        if e <= s:
            continue
        op_ns[op] = op_ns.get(op, 0.0) + (e - s)
        (comm if is_collective(op) else ops).append((s, e))
        sc = smap.scopes.get(op) if smap else None
        if sc:
            i = bisect.bisect_right(run_starts, s) - 1
            if i >= 0 and s < runs[i][1]:
                by_scope.setdefault(sc, []).append((s, e))
    busy = union(ops + comm)
    exposed = subtract(union(comm), union(ops))
    return Chip(name, total(busy), total(exposed), op_ns,
                subtract([(w0, w1)], busy),
                {k: total(union(v)) for k, v in by_scope.items()})


def reduce(path: str, smap=None) -> Summary:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    spans = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(HOST_SPANS):
                    spans.append((ev.start_ns, ev.end_ns, ev.name))
    windows = [(s, e) for s, e, n in spans if n == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"{len(windows)} {WINDOW_SPAN} spans in {path}")
    w0, w1 = windows[0]
    dispatched = min((e for s, e, n in spans if n == DISPATCH_SPAN),
                     default=w0)

    chips = []
    for plane in sorted(pd.planes, key=lambda p: p.name):
        if not plane.name.startswith(DEVICE_PLANE):
            continue
        lines = {line.name: list(line.events) for line in plane.lines}
        modules = lines.get(MODULES_LINE, [])
        first = min((ev.start_ns for ev in modules), default=dispatched)
        shift = dispatched - first
        events = [(ev.start_ns + shift, ev.end_ns + shift, op_name(ev.name))
                  for ev in lines.get(OPS_LINE, [])]
        runs = [(ev.start_ns + shift, ev.end_ns + shift) for ev in modules
                if smap and ev.name.split("(", 1)[0] == smap.module]
        chips.append(chip_summary(plane.name, events, (w0, w1), smap, runs))
    if not chips:
        raise ValueError(f"no {DEVICE_PLANE}* plane in {path}")
    return Summary(float(w1 - w0), chips, spans)
