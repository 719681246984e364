"""The per-layer readers on hand-made inputs, each with what it reads and
without it; and a model family joining the benchmark as new files only."""

import sys
import types

import pytest

from benchmarks.chip import flops, harness, run, scopes, trace

PEAKS = harness.load_json(harness.HERE / "peaks.json")["devices"][
    "TPU v5 lite"]

# two chips, seconds per step by scope
SCOPE_S = {"attention": [0.29, 0.3], "mlp": [0.15, 0.16],
           "head_ce": [0.0, 0.0146], "optimizer": [0.0196, 0.019],
           "pipeline": [0.0358, 0.03]}


@pytest.mark.parametrize("name, scope", [
    ("attention_ms", "attention"), ("mlp_ms", "mlp"),
    ("head_ce_ms", "head_ce"), ("optimizer_ms", "optimizer"),
    ("pipeline_self_ms", "pipeline")])
def test_scope_time_readers(name, scope):
    reader = harness.metric_reader(name)
    assert reader.read({"scope_s": SCOPE_S}) == pytest.approx(
        1e3 * max(SCOPE_S[scope]))
    others = {k: v for k, v in SCOPE_S.items() if k != scope}
    assert reader.read({"scope_s": others}) is None
    assert reader.read({}) is None


def test_mlp_roofline():
    """One chip's MLP: 2.97e13 FLOPs a step at 197 TFLOP/s is 151 ms, read
    against 220 ms of the ``mlp`` scope; on several chips against their
    sum, with the work of every slot the tick scan computes."""
    reader = harness.metric_reader("mlp_roofline")
    m = harness.load_json(harness.HERE / "configs" / "phi3-mini-l4.json")[
        "model"]
    inputs = {"scope_work": flops.scope_work("dense_swiglu", m, 2048),
              "seq": 2048, "global_batch": 8, "peaks": PEAKS,
              "scope_s": {"mlp": [0.22]},
              "counters": {"slots_real": 32, "slots_computed": 32}}
    one = reader.read(inputs)
    assert one == pytest.approx(100 * 2.97e13 / 197e12 / 0.22, rel=1e-3)
    assert 60 < one < 100
    inputs["scope_s"] = {"mlp": [0.11, 0.11]}
    assert reader.read(inputs) == pytest.approx(one)
    # fill and drain: 88 slots computed for 64 real ones, in 88/64 the time
    inputs["scope_s"] = {"mlp": [0.11 * 88 / 64] * 2}
    inputs["counters"] = {"slots_real": 64, "slots_computed": 88}
    assert reader.read(inputs) == pytest.approx(one)
    for key in ("scope_work", "scope_s", "peaks", "counters"):
        assert reader.read({**inputs, key: None}) is None
    assert reader.read({**inputs, "scope_s": {"attention": [1.0]}}) is None


def test_slot_use_pct():
    reader = harness.metric_reader("slot_use_pct")
    assert reader.read({"counters": {"slots_real": 64,
                                     "slots_computed": 88}}) \
        == pytest.approx(72.7272727)
    assert reader.read({"counters": {}}) is None
    assert reader.read({}) is None


def test_a_family_joins_with_new_files_only(monkeypatch):
    """A family module and a reader module that no file of the benchmark
    names, put where a new file would be found: the FLOP count finds the
    one, and the harness hands the other the scope times and counters."""
    fam = types.ModuleType("benchmarks.chip.flops.toy_moe")
    fam.matmul_params = lambda m: m["d_model"] * m["active"]
    fam.mixing_flops_forward = lambda m, seq: 10.0 * seq
    fam.scope_work = lambda m, seq: {"moe": (6.0 * m["d_model"], 0.0)}
    monkeypatch.setitem(sys.modules, fam.__name__, fam)
    reader = types.ModuleType("benchmarks.chip.metrics.toy_moe_use")
    reader.read = lambda inputs: (
        1e3 * max(inputs["scope_s"]["moe"])
        * inputs["counters"]["held_experts"]
        / inputs["scope_work"]["moe"][0])
    monkeypatch.setitem(sys.modules, reader.__name__, reader)

    model = {"d_model": 4, "active": 3}
    assert flops.train_flops_per_token("toy_moe", model, 8) == 3.0 * (
        2.0 * 12 + 80.0)
    cell = harness.Cell(
        name="toy", chips=1, config={}, traffic={}, limits={"loss_gap": 1.0},
        end_to_end=[], per_layer=[{"name": "toy_moe_use", "unit": "ms"}])
    out = {"trace": trace.Summary(1e9, [trace.Chip("c", 5e8, 0.0, {}, [])],
                                  []),
           "memory_peak_bytes": 1, "attempted": 3, "failed": 0,
           "checks": {"loss_gap": (0.5, "step 1")},
           "layer_inputs": {"scope_s": {"moe": [0.25]},
                            "counters": {"held_experts": 8},
                            "scope_work": flops.scope_work("toy_moe", model,
                                                           8)}}
    dev = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    line = run.result_line(cell, out, [dev], traced=True, peaks=PEAKS)
    assert line["metrics"] == {"toy_moe_use": {
        "value": pytest.approx(1e3 * 0.25 * 8 / 24), "unit": "ms"}}
    assert line["correct"]


CELLS = [w["name"] for w in
         harness.load_json(harness.ROOT / "BENCHMARK.json")["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_traced_rehearsal_reads_every_per_layer_metric(monkeypatch, name):
    """A traced run at smoke sizes on the CPU, whose profiler records no
    device: the trace is replaced by one that gives each scope of the
    step's map 0.1 s on each chip.  Every per-layer metric of the cell is
    read, from the inputs the job hands over."""
    def fake_traced(fn, smap=None):
        names = {v for v in smap.scopes.values() if v}
        chips = [trace.Chip(f"c{i}", 5e8, 1e6, {"fusion.1": 5e8}, [],
                            {k: 1e8 for k in names})
                 for i in range(cell.chips)]
        return fn(), trace.Summary(1e9, chips, [])

    seen = {}
    real_reader = harness.metric_reader

    def spy(metric):
        def read(inputs):
            seen.update(inputs)
            return real_reader(metric).read(inputs)
        return types.SimpleNamespace(read=read)

    cell = harness.Cell.load(name).rehearsal()
    monkeypatch.setattr(trace, "traced", fake_traced)
    monkeypatch.setattr(harness, "peaks", lambda kind: PEAKS)
    monkeypatch.setattr(harness, "metric_reader", spy)
    line, _, _ = run.measure(cell, 2 ** 31 + 29, 0.5, True,
                             require_tpu=False)
    assert line["correct"], line["checks"]
    assert set(line["metrics"]) == {m["name"] for m in cell.per_layer}
    assert line["metrics"]["attention_ms"]["value"] == pytest.approx(
        1e3 * 0.1 / 3)
    assert seen["family"] == "dense_swiglu"
    assert seen["model"] == cell.config["model"]
    assert seen["seq"] == 64 and seen["global_batch"] == 8
    assert set(seen["step_metrics"]) == {"ce", "aux", "mtp", "tokens"}
    assert seen["step_metrics"]["tokens"] > 0
    if cell.chips == 4:
        assert seen["counters"] == {"slots_real": 64, "slots_computed": 88}
        assert line["metrics"]["slot_use_pct"]["value"] == pytest.approx(
            100 * 64 / 88)


@pytest.mark.parametrize("fault", ["no scope in the text",
                                   "scopes under the floor"])
def test_traced_run_refuses_a_map_that_misses(monkeypatch, fault):
    """A traced run stops with no result where the step's compiled text
    names no scope (a compile-cache entry built without them), or where
    its scopes cover under ``SCOPED_FLOOR`` of a chip's busy time (the
    map's names miss the trace's): the scope metrics would read low or not
    at all, and nothing else would say so."""
    def fake_traced(fn, smap=None):
        chips = [trace.Chip("c0", 1e9, 0.0, {"fusion.1": 1e9}, [],
                            {"attention": 0.5e9, "mlp": 0.39e9})]
        return fn(), trace.Summary(1e9, chips, [])

    if fault == "no scope in the text":
        monkeypatch.setattr(scopes, "compiled_text",
                            lambda *a: "HloModule jit_step_fn\n")
    monkeypatch.setattr(trace, "traced", fake_traced)
    monkeypatch.setattr(harness, "peaks", lambda kind: PEAKS)
    cell = harness.Cell.load("phi3-mini.train.1chip").rehearsal()
    with pytest.raises(SystemExit, match="no program scope" if fault ==
                       "no scope in the text" else "cover 89.00%"):
        run.measure(cell, 2 ** 31 + 31, 0.5, True, require_tpu=False)
