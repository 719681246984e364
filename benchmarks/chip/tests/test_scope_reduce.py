"""Device time by program scope (``scopes.py``): the innermost scope of an
``op_name``, the map from HLO text, per-scope unions on synthetic events,
and a small trace recorded on a TPU v5e of the smoke-size phi3 step (one
chip, three steps), with the compiled HLO text of that step."""

import gzip
import json
from pathlib import Path

import pytest

from benchmarks.chip import scopes, trace
from repro import scopes as program_scopes

DATA = Path(__file__).parent / "data"
RECORDED = DATA / "scoped_smoke_v5e.xplane.pb.gz"
RECORDED_HLO = DATA / "scoped_smoke_v5e.hlo.txt.gz"


@pytest.mark.parametrize("path, want", [
    ("jit(step_fn)/jvp()/shard_map/asteroid/pipeline/while/body/"
     "dynamic_update_slice", "pipeline"),
    # backward: transpose of the forward, under the same scopes
    ("jit(step_fn)/transpose(jvp())/shard_map/asteroid/pipeline/while/body/"
     "closed_call/asteroid/stage/while/body/closed_call/checkpoint/"
     "asteroid/mlp/add_any", "mlp"),
    # the wrappers around the scope, as older JAX prints them
    ("jit(step_fn)/transpose(jvp(asteroid/pipeline))/while/body/"
     "asteroid/attention/dot_general", "attention"),
    ("transpose(jvp(asteroid/grad_reduce))/psum", "grad_reduce"),
    # remat recompute keeps the scope of the op it recomputes
    ("jit(step_fn)/asteroid/stage/checkpoint/rematted_computation/"
     "asteroid/attention/exp", "attention"),
    ("jit(step_fn)/asteroid/stage/checkpoint/rematted_computation/mul",
     "stage"),
    ("jit(step_fn)/jvp()/closed_call/while/body/add", None),
    ("", None),
])
def test_innermost_scope(path, want):
    assert scopes.innermost(path) == want


HLO = """HloModule jit_step_fn, is_scheduled=true, entry_computation_layout={()}

%fused_computation (param_0: f32[2]) -> f32[2] {
  %param_0 = f32[2]{0} parameter(0)
  ROOT %multiply.1 = f32[2]{0} multiply(%param_0, %param_0), metadata={op_name="jit(step_fn)/asteroid/optimizer/mul"}
}

ENTRY %main (p: f32[2]) -> f32[2] {
  %p = f32[2]{0} parameter(0)
  %fusion.3 = f32[2]{0} fusion(%p), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step_fn)/transpose(jvp())/asteroid/pipeline/while/body/asteroid/mlp/dot_general" source_file="x.py" source_line=3}
  %all-reduce-start.1 = f32[2]{0} all-reduce-start(%fusion.3), replica_groups={{0}}, metadata={op_name="jit(step_fn)/asteroid/grad_reduce/psum"}
  %copy.2 = f32[2]{0} copy(%p), metadata={op_name="jit(step_fn)/copy"}
  ROOT %tuple = (f32[2]{0}) tuple(%copy.2)
}
"""


def test_scope_map_from_hlo_text():
    smap = scopes.scope_map(HLO)
    assert smap.module == "jit_step_fn"
    assert smap.scopes == {"multiply.1": "optimizer", "fusion.3": "mlp",
                           "all-reduce-start.1": "grad_reduce",
                           "copy.2": None}
    with pytest.raises(ValueError):
        scopes.scope_map("ENTRY %main () -> f32[] {}")


SMAP = scopes.ScopeMap("jit_step_fn", {
    "fusion.1": "attention", "fusion.2": "mlp", "all-reduce.1": "grad_reduce",
    "collective-permute-done.1": "boundary", "copy.1": None,
    "fusion.9": "pipeline"})


def test_scope_unions_with_overlapping_async_ops():
    """An async collective that overlaps compute counts in both scopes'
    unions; two ops of one scope that overlap count once; an op outside
    the step's module, or of a name the map does not know, has no scope."""
    events = [(0, 300, "while.1"),                 # encloses: not a leaf
              (10, 50, "fusion.1"), (40, 80, "fusion.1"),      # overlap
              (60, 120, "all-reduce.1"),           # async, over both
              (100, 150, "fusion.2"),
              (160, 170, "copy.1"),                # mapped, no scope
              (170, 180, "fusion.7"),              # not in the map
              (200, 220, "collective-permute-done.1"),
              (350, 360, "fusion.9")]              # another module
    c = scopes.chip_scopes("c", events, [(0, 300)], (0, 400), SMAP)
    base = trace.chip_summary("c", events, (0, 400))
    assert c.busy_ns == base.busy_ns == 70 + 70 + 20 + 20 + 10
    assert c.scope_ns == {"attention": 70, "grad_reduce": 60, "mlp": 50,
                          "boundary": 20}
    assert c.scoped_ns == 140 + 20
    assert c.unscoped_ns == 10 + 10 + 10
    assert c.unscoped_op_ns == {"copy.1": 10, "fusion.7": 10,
                                "fusion.9": 10}
    assert c.step_ns == 40 + 40 + 60 + 50 + 10 + 10 + 20
    assert c.matched_ns == c.step_ns - 10
    # the gaps, each with the scope of the op that ends it
    assert c.gaps == [(0, 10, "attention", "fusion.1"),
                      (150, 160, None, "copy.1"),
                      (180, 200, "boundary", "collective-permute-done.1"),
                      (220, 350, None, "fusion.9"), (360, 400, None, None)]
    assert c.scope_gaps() == {"attention": 10, "boundary": 20,
                              None: 10 + 130 + 40}


def test_scope_unions_clip_to_the_window():
    events = [(0, 100, "fusion.1"), (90, 200, "fusion.2")]
    c = scopes.chip_scopes("c", events, [(0, 200)], (50, 150), SMAP)
    assert c.scope_ns == {"attention": 50, "mlp": 60}
    assert c.busy_ns == 100
    assert c.gaps == []


def test_clock_residuals():
    c = scopes.chip_scopes("c", [(0, 10, "fusion.1")], [(0, 10)], (0, 20),
                           SMAP, callbacks=[(10, 13), (30, 31)])
    assert c.clock_residual_ns == [3, 1]


def test_per_step_shares():
    events = [(0, 60, "fusion.1"), (60, 90, "copy.1"), (95, 100, "fusion.2")]
    c = scopes.chip_scopes("c", events, [(0, 100)], (0, 100), SMAP)
    out = scopes.per_step(c, steps=1)
    assert out["busy_ms"] == pytest.approx(95e-6)
    assert out["scope_ms"] == {"attention": pytest.approx(60e-6),
                               "mlp": pytest.approx(5e-6)}
    assert out["scoped_pct"] == pytest.approx(100 * 65 / 95)
    assert out["sum_over_busy_pct"] == pytest.approx(100.0)
    assert out["idle_before_ms"] == {"mlp": pytest.approx(5e-6)}
    assert out["top_unscoped_ms"] == [["copy.1", pytest.approx(30e-6)]]


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "scoped.xplane.pb"
    with gzip.open(RECORDED) as f:
        path.write_bytes(f.read())
    with gzip.open(RECORDED_HLO, "rt") as f:
        smap = scopes.scope_map(f.read())
    return path, smap, scopes.reduce(str(path), smap)


def test_recorded_step_is_scoped(recorded):
    path, smap, br = recorded
    assert smap.module == "jit_step_fn"
    # the benchmark and the program name the scopes alike; on one chip the
    # compiler drops the gradient psums over size-1 axes, and there is no
    # last-stage redistribution
    assert {s for s in smap.scopes.values() if s} == set(
        program_scopes.ALL) - {program_scopes.REDISTRIBUTE,
                               program_scopes.GRAD_REDUCE}
    (chip,) = br.chips
    # the same window, clock shift and leaf ops as trace.reduce
    summary = trace.reduce(str(path))
    assert br.window_ns == summary.window_ns
    assert chip.busy_ns == summary.chips[0].busy_ns
    # the map knows every step op that carries metadata: all but a few
    # copies XLA adds
    assert 0.9 * chip.step_ns <= chip.matched_ns <= chip.step_ns
    assert chip.scoped_ns >= 0.9 * chip.busy_ns
    parts = sum(chip.scope_ns.values()) + chip.unscoped_ns
    assert parts == pytest.approx(chip.busy_ns, rel=0.01)


def test_recorded_clock_check(recorded):
    """The callback that reports a step done starts after the step ends on
    the shifted device clock, within a millisecond."""
    *_, br = recorded
    (chip,) = br.chips
    assert len(chip.clock_residual_ns) == 3
    assert all(0 <= r < 1e6 for r in chip.clock_residual_ns)


def test_recorded_gaps_name_the_program_span(recorded):
    *_, br = recorded
    names = {sp[2] for sp in br.host_spans}
    assert program_scopes.SHARD_BATCH_SPAN in names
    assert trace.DISPATCH_SPAN in names


@pytest.fixture
def no_compile_cache():
    """A cache entry of the same step compiled without scopes has the same
    key and would hand back text without them: compile afresh."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def test_rehearsal_maps_the_step_and_counts_slots(tmp_path, no_compile_cache):
    """Off the chip the script runs the cell's step at smoke sizes, maps
    its compiled text and counts its slots, then refuses to give a
    result."""
    with pytest.raises(SystemExit, match="not a chip run"):
        scopes.main(["--workload", "phi3-mini.pipeline.4chip",
                     "--seed", "3000000007", "--rehearse",
                     "--out", str(tmp_path)])
    (path,) = tmp_path.glob("phi3-mini.pipeline.4chip.smoke.*.json")
    out = json.loads(path.read_text())
    assert out["module"] == "jit_step_fn"
    assert out["scopes_in_text"] == sorted(program_scopes.ALL)
    # the smoke plan splits 8 layers (2|3|2|1): 3 slots for 11 ticks
    assert out["slots"] == [64, 132]
    assert "chips" not in out


def test_script_refuses_text_without_scopes(tmp_path, monkeypatch):
    """A step text that names no scope (a cache entry compiled without
    them) is no breakdown: the script exits non-zero."""
    monkeypatch.setattr(scopes, "measure",
                        lambda *a: {"scopes_in_text": []})
    with pytest.raises(SystemExit, match="names no scope"):
        scopes.main(["--workload", "phi3-mini.train.1chip",
                     "--seed", "3000000011", "--rehearse",
                     "--out", str(tmp_path)])
