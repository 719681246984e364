"""``correct`` at smoke sizes on the CPU: a sound run passes, and a run
with the timed path broken underneath, or the control in the program's
place, does not.

The run is ``run.measure`` with the look for a chip skipped; each fault is
planted in what ``core.lowering.plan_to_train_step`` hands the job, so the
harness itself is the one that runs.
"""

import dataclasses

import pytest

from benchmarks.chip import calibrate, harness, run
from benchmarks.chip.jobs import train

CELLS = [w["name"] for w in
         harness.load_json(harness.ROOT / "BENCHMARK.json")["workloads"]]
SEED = 2 ** 31 + 17


def rehearsal(name):
    return harness.Cell.load(name).rehearsal()


def plant(monkeypatch, fault: str) -> None:
    from repro.core import lowering

    if fault == "exchange":
        monkeypatch.setattr("repro.runtime.pipeline.lax",
                            calibrate.exchange_shim())
        return
    original = lowering.plan_to_train_step

    def broken(*args, **kw):
        ts, lowered = original(*args, **kw)
        step = ts.step_fn
        if fault == "frozen":
            def step_fn(params, opt_state, batch):
                _, _, loss, metrics = step(params, opt_state, batch)
                return params, opt_state, loss, metrics
        else:
            step_fn = calibrate.half_batch(step)
        return dataclasses.replace(ts, step_fn=step_fn), lowered

    monkeypatch.setattr(lowering, "plan_to_train_step", broken)


def faults(name):
    out = ["frozen", "half_batch"]
    if harness.Cell.load(name).chips > 1:
        out.append("exchange")
    return out


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    line, devs, _ = run.measure(rehearsal(name), SEED, 0.5, False,
                                require_tpu=False)
    assert devs[0].platform == "cpu"
    assert line["correct"], line["checks"]
    assert list(line)[-1] == "checks"
    assert line["metrics"]["train_tok_s"]["value"] > 0


@pytest.mark.parametrize("name,fault", [(n, f) for n in CELLS
                                        for f in faults(n)])
def test_broken_step_is_not_correct(monkeypatch, name, fault):
    plant(monkeypatch, fault)
    line, _, _ = run.measure(rehearsal(name), SEED, 0.5, False,
                             require_tpu=False)
    assert not line["correct"], line["checks"]


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name):
    """The reference with its matmul operands in int8, put in the
    program's place, against the cell's limits."""
    cell = rehearsal(name)
    devs = harness.take_devices(cell.chips, require_tpu=False)
    control = calibrate.AsProgram(train.Reference(cell, devs, "int8"), cell)
    ds = control.stream(SEED)
    _, got = control.checked_steps(SEED, ds)
    want = train.Reference(cell, devs).readings(
        SEED, [ds.batch(s, cell.traffic["global_batch"])
               for s in range(train.CHECKED_STEPS)])
    checks = train.compare(got, want)
    assert any(checks[k][0] > limit for k, limit in cell.limits.items()), \
        checks


def test_no_tpu_exits_without_result(capsys):
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1"])
    assert e.value.code != 0
    assert "no TPU" in str(e.value.code)
    assert capsys.readouterr().out == ""


def test_rwkv6_reference_matches_the_program():
    """The RWKV-6 reference, which no cell uses yet, against the program at
    smoke sizes on the CPU, where both compute in exact float32."""
    cell = harness.Cell(
        name="rwkv6-7b.smoke", chips=1,
        config=harness.load_json(harness.HERE / "configs" / "rwkv6-7b-l2.json"),
        traffic=harness.load_json(harness.HERE / "traffic"
                                  / "lm-s2048-b8-m8.json"),
        limits={}, end_to_end=[], per_layer=[]).rehearsal()
    devs = harness.take_devices(1, require_tpu=False)
    prog = train.Program(cell, devs, lambda msg: None)
    ds = prog.stream(SEED)
    _, got = prog.checked_steps(SEED, ds)
    want = train.Reference(cell, devs).readings(
        SEED, [ds.batch(s, cell.traffic["global_batch"])
               for s in range(train.CHECKED_STEPS)])
    checks = train.compare(got, want)
    assert checks["loss_gap"][0] < 1e-5, checks
    assert checks["change_gap"][0] < 1e-3, checks
