"""The trace reduction, on interval arithmetic and on two small traces
recorded on a TPU v5e: five 91 us matmul fusions on one chip, each after
about 4 ms of host work inside ``bench.make_batch``; and three steps of the
smoke-size phi3 train step on one chip, with the compiled HLO text of that
step."""

import dataclasses
import gzip
from pathlib import Path

import pytest

from benchmarks.chip import scopes, trace
from repro import scopes as program_scopes

DATA = Path(__file__).parent / "data"
RECORDED = DATA / "one_chip_v5e.xplane.pb"
SCOPED = DATA / "scoped_smoke_v5e.xplane.pb.gz"
SCOPED_HLO = DATA / "scoped_smoke_v5e.hlo.txt.gz"


def test_union_merges_overlaps():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


def test_subtract():
    a = [(0, 10), (20, 30)]
    b = [(2, 3), (5, 22), (29, 40)]
    assert trace.subtract(a, b) == [(0, 2), (3, 5), (22, 29)]
    assert trace.subtract(a, []) == a
    assert trace.subtract([(0, 1)], [(0, 1)]) == []


def test_leaves_drop_enclosing_events():
    events = [(0, 100, "while.1"), (10, 20, "fusion.1"), (30, 90, "while.2"),
              (40, 50, "all-reduce.1"), (60, 70, "fusion.2"),
              (120, 130, "fusion.3")]
    assert sorted(trace.leaves(events)) == [
        (10, 20, "fusion.1"), (40, 50, "all-reduce.1"), (60, 70, "fusion.2"),
        (120, 130, "fusion.3")]


def test_op_names_and_collectives():
    text = ("%all-reduce-start.3 = f32[8]{0} all-reduce-start(f32[8]{0} "
            "%fusion.2), replica_groups={{0,1}}")
    assert trace.op_name(text) == "all-reduce-start.3"
    assert trace.is_collective("collective-permute-done.1")
    assert trace.is_collective("all-to-all.4")
    # an op that only reads a collective's output is not one
    assert not trace.is_collective(trace.op_name(
        "%fusion.5 = f32[8]{0} fusion(f32[8]{0} %all-reduce.3)"))


def test_chip_summary_counts_leaf_ops_only():
    """Idle time inside a ``while`` (between the ops of its body) is idle,
    and ops are clipped to the window only once the leaves are known."""
    events = [(0, 100, "while.1"), (10, 20, "fusion.1"),
              (60, 70, "collective-permute-done.1"), (65, 80, "fusion.2"),
              (90, 105, "fusion.3"),
              (110, 200, "while.2"), (150, 160, "fusion.4")]
    c = trace.chip_summary("c", events, (5, 120))
    assert c.busy_ns == 10 + 20 + 15
    assert c.exposed_collective_ns == 5
    assert c.gaps == [(5, 10), (20, 60), (80, 90), (105, 120)]
    assert c.op_ns == {"fusion.1": 10, "collective-permute-done.1": 10,
                       "fusion.2": 15, "fusion.3": 15}


def chip(busy, comm):
    return trace.Chip("c", busy, comm, {}, [])


def test_summary_shares():
    s = trace.Summary(100.0, [chip(80.0, 5.0), chip(60.0, 0.0)], [])
    assert s.idle_share() == pytest.approx(0.4)
    assert s.busy_s == pytest.approx(70e-9)


def test_host_activity_is_the_innermost_span():
    spans = [(0, 100, trace.WINDOW_SPAN), (10, 20, "bench.make_batch")]
    s = trace.Summary(100.0, [], spans)
    assert s.host_activity(15) == "bench.make_batch"
    assert s.host_activity(50) == trace.WINDOW_SPAN
    assert s.host_activity(150) == "outside any bench span"


@pytest.fixture(scope="module")
def recorded():
    return trace.reduce(str(RECORDED))


def test_recorded_window_and_busy_time(recorded):
    assert len(recorded.chips) == 1
    assert recorded.window_ns == pytest.approx(19_784_229)
    # five fusions of 91.507 us and their small copies
    assert recorded.busy_s == pytest.approx(5 * 91.5e-6, rel=0.01)
    assert recorded.idle_share() == pytest.approx(1 - recorded.busy_s * 1e9
                                                  / recorded.window_ns)
    assert recorded.chips[0].exposed_collective_ns == 0.0


def test_recorded_breakdown(recorded):
    (name, secs), *_ = recorded.top_ops()
    assert name == "fusion"
    assert secs == pytest.approx(5 * 91.5e-6, rel=0.01)
    gaps = recorded.longest_gaps()
    assert len(gaps) == 10
    assert gaps[0][0] == "bench.make_batch"
    assert 3e-3 < gaps[0][1] < 6e-3


@pytest.fixture(scope="module")
def scoped(tmp_path_factory):
    """The smoke step's trace reduced without and with its scope map."""
    path = tmp_path_factory.mktemp("trace") / "scoped.xplane.pb"
    with gzip.open(SCOPED) as f:
        path.write_bytes(f.read())
    with gzip.open(SCOPED_HLO, "rt") as f:
        smap = scopes.scope_map(f.read())
    return smap, trace.reduce(str(path)), trace.reduce(str(path), smap)


def test_scope_map_changes_no_other_number(scoped):
    """Window, clock shift and leaf ops are those of the reduction without
    the map; the numbers the metrics read stay as they were before the
    scopes were added to it, to the bit."""
    _, plain, mapped = scoped
    assert dataclasses.replace(mapped, chips=[]) == dataclasses.replace(
        plain, chips=[])
    assert [dataclasses.replace(c, scope_ns={}) for c in mapped.chips] \
        == plain.chips
    assert mapped.window_ns == 9250399.0
    assert [c.busy_ns for c in mapped.chips] == [3200323.0]
    assert [c.exposed_collective_ns for c in mapped.chips] == [409.0]
    assert mapped.idle_share() == 0.6540340584227773


def test_recorded_time_by_scope(scoped):
    """Each scope's union of the leaf ops whose innermost scope it is, over
    the step's module: on one chip the compiler drops the gradient psums
    over size-1 axes, and there is no last-stage redistribution."""
    smap, _, mapped = scoped
    assert smap.module == "jit_step_fn"
    assert {s for s in smap.scopes.values() if s} == set(
        program_scopes.ALL) - {program_scopes.REDISTRIBUTE,
                               program_scopes.GRAD_REDUCE}
    (chip,) = mapped.chips
    assert chip.scope_ns == {
        "pipeline": 118983.0, "attention": 2474388.0, "stage": 137776.0,
        "mlp": 290335.0, "head_ce": 35375.0, "optimizer": 30451.0,
        "boundary": 409.0, "embed": 11299.0}
    # all but a few copies XLA adds carry a scope
    assert 0.9 * chip.busy_ns <= sum(chip.scope_ns.values()) <= chip.busy_ns
    assert mapped.scoped_shares() == [sum(chip.scope_ns.values())
                                      / chip.busy_ns]
    assert mapped.scoped_shares()[0] >= scopes.SCOPED_FLOOR
    assert scoped[1].scoped_shares() == [0.0]
    assert mapped.scope_s(3)["attention"] == [2474388.0 / 1e9 / 3]


def test_recorded_gaps_name_the_program_span(scoped):
    """The program's host span around ``TrainStep.shard_batch`` is kept
    beside the benchmark's, so a gap under it is named by it."""
    _, _, mapped = scoped
    names = {sp[2] for sp in mapped.host_spans}
    assert program_scopes.SHARD_BATCH_SPAN in names
    assert trace.DISPATCH_SPAN in names
    s, e, _ = next(sp for sp in mapped.host_spans
                   if sp[2] == program_scopes.SHARD_BATCH_SPAN)
    assert mapped.host_activity((s + e) / 2) \
        == program_scopes.SHARD_BATCH_SPAN
