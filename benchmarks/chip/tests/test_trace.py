"""The trace reduction, on interval arithmetic and on a small trace
recorded on a TPU v5e (one chip: five 91 us matmul fusions, each after
about 4 ms of host work inside ``bench.make_batch``)."""

from pathlib import Path

import pytest

from benchmarks.chip import trace

RECORDED = Path(__file__).parent / "data" / "one_chip_v5e.xplane.pb"


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
