"""Device time by program scope: the innermost scope of an ``op_name``,
the map from HLO text (``scopes.py``), per-scope unions on synthetic events
(``trace.chip_summary``), and the train job's step at smoke sizes, whose
compiled text names every scope and whose period slots are counted."""

import dataclasses

import jax
import pytest

from benchmarks.chip import harness, scopes, trace
from benchmarks.chip.jobs import train
from repro import scopes as program_scopes


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
    the step's module, or of a name the map does not know, has no scope.
    The scopes change nothing else of the chip's numbers."""
    events = [(0, 300, "while.1"),                 # encloses: not a leaf
              (10, 50, "fusion.1"), (40, 80, "fusion.1"),      # overlap
              (60, 120, "all-reduce.1"),           # async, over both
              (100, 150, "fusion.2"),
              (160, 170, "copy.1"),                # mapped, no scope
              (170, 180, "fusion.7"),              # not in the map
              (200, 220, "collective-permute-done.1"),
              (350, 360, "fusion.9")]              # another module
    c = trace.chip_summary("c", events, (0, 400), SMAP, [(0, 300)])
    base = trace.chip_summary("c", events, (0, 400))
    assert c.scope_ns == {"attention": 70, "grad_reduce": 60, "mlp": 50,
                          "boundary": 20}
    assert base.scope_ns == {}
    assert dataclasses.replace(c, scope_ns={}) == base
    assert c.busy_ns == 70 + 70 + 20 + 20 + 10


def test_scope_unions_clip_to_the_window():
    events = [(0, 100, "fusion.1"), (90, 200, "fusion.2")]
    c = trace.chip_summary("c", events, (50, 150), SMAP, [(0, 200)])
    assert c.scope_ns == {"attention": 50, "mlp": 60}
    assert c.busy_ns == 100
    assert c.gaps == []


def test_scope_seconds_per_step():
    chips = [trace.Chip("a", 0.0, 0.0, {}, [], {"mlp": 3e9, "stage": 6e9}),
             trace.Chip("b", 0.0, 0.0, {}, [], {"mlp": 1.5e9})]
    s = trace.Summary(1e10, chips, [])
    assert s.scope_s(3) == {"mlp": [1.0, 0.5], "stage": [2.0, 0.0]}
    assert trace.Summary(1e10, [trace.Chip("a", 0.0, 0.0, {}, [])],
                         []).scope_s(3) == {}


@pytest.fixture
def no_compile_cache():
    """A cache entry of the same step compiled without scopes has the same
    key and would hand back text without them: compile afresh."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def test_rehearsal_maps_the_step_and_counts_slots(no_compile_cache):
    """The four-chip cell's step at smoke sizes on four CPU devices: the
    job's counters give its period slots, and the step's compiled text, as
    a traced run maps it, names every scope of the program."""
    cell = harness.Cell.load("phi3-mini.pipeline.4chip").rehearsal()
    prog = train.Program(cell, jax.devices()[:4], lambda msg: None)
    # the smoke plan deploys 8 layers as 2|2|2|2: 2 slots for 11 ticks
    assert train.program_counters(prog.ts.spec) == {
        "slots_real": 64, "slots_computed": 88}
    key = jax.random.PRNGKey(0)
    params = prog._make(key)
    state = (params, prog._opt_init(params))
    batch = prog.ts.shard_batch(prog.stream(3000000007).batch(
        0, cell.traffic["global_batch"]))
    smap = scopes.scope_map(scopes.compiled_text(prog.step_fn, *state,
                                                 batch))
    assert smap.module == "jit_step_fn"
    assert {v for v in smap.scopes.values() if v} == set(program_scopes.ALL)
