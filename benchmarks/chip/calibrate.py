"""The readings that the limits of ``correct`` are set from, in one process.

    python3 benchmarks/chip/calibrate.py --workload <name> \
        --read program=101-112 --read bf16_compute=121-126 \
        --read control=201-203 --read half_batch=301-303

For each seed of each kind it trains the cell's checked steps through what
the kind puts in the program's place, as a run does, then the plain
reference, and prints one JSON line with the numbers ``correct`` compares.
The kinds:

* ``program``: the program as the cell runs it;
* ``bf16_compute``: the program with ``compute_dtype`` bfloat16, so that
  activations, the residual stream and the stage-boundary transfers are
  bfloat16.  The configuration states bfloat16 compute, so this is a sound
  run too, and its readings count toward the lower ones;
* ``bf16_operands``: the reference with every matmul operand, and its
  gradient, rounded to bfloat16, put in the program's place: the stated
  precision, computed plainly;
* ``control``: the reference with every matmul's operands in int8 (one
  scale per tensor), put in the program's place: the step below the
  stated bfloat16;
* ``half_batch``: each step fed half of its batch twice, so the loss is
  the mean over the first half and the second half is left out;
* ``exchange``: the pipeline's stage-boundary ``ppermute`` returns its
  input unchanged, so no stage receives the previous stage's activations
  (meaningful on more than one chip);

and ``frozen``, a step that returns its state unchanged, needs no run: its
param change is 0, so ``change_gap`` reads 1 on every row the reference
moves.  ``--rehearse`` runs at the smoke sizes on any platform.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from benchmarks.chip import harness  # noqa: E402


def seed_list(spec: str) -> list:
    out = []
    for part in filter(None, spec.split(",")):
        lo, _, hi = part.partition("-")
        out += list(range(int(lo), int(hi) + 1)) if hi else [int(lo)]
    return out


def half_batch(step_fn):
    """``step_fn`` fed the first half of each batch twice."""
    import jax.numpy as jnp

    def step(params, opt_state, batch):
        t = batch["tokens"]
        half = t[: t.shape[0] // 2]
        return step_fn(params, opt_state,
                       {"tokens": jnp.concatenate([half, half], axis=0)})

    return step


def exchange_shim():
    """``jax.lax`` with a ``ppermute`` that returns its input: put in the
    pipeline's place, no stage receives its neighbour's activations."""
    from jax import lax

    shim = types.SimpleNamespace(**{k: getattr(lax, k) for k in dir(lax)
                                    if not k.startswith("__")})
    shim.ppermute = lambda x, axis_name, perm: x
    return shim


class AsProgram:
    """A reference put in the program's place."""

    def __init__(self, reference, cell):
        from benchmarks.chip.stream import BigramStream

        self.reference, self.cell = reference, cell
        self._stream = BigramStream
        self.gb = cell.traffic["global_batch"]

    def stream(self, seed):
        return self._stream(self.cell.config["model"]["vocab_size"],
                            self.cell.traffic["seq"], seed)

    def checked_steps(self, seed, ds):
        from benchmarks.chip.jobs.train import CHECKED_STEPS

        return None, self.reference.readings(
            seed, [ds.batch(s, self.gb) for s in range(CHECKED_STEPS)])


def stand_in(kind: str, cell, devs, log, train):
    """What ``kind`` puts in the program's place."""
    if kind in ("control", "bf16_operands"):
        precision = "int8" if kind == "control" else "bfloat16"
        return AsProgram(train.Reference(cell, devs, precision), cell)
    if kind == "bf16_compute":
        cell = dataclasses.replace(cell, config=harness.merge(
            cell.config, {"model": {"compute_dtype": "bfloat16"}}))
    prog = train.Program(cell, devs, log)
    if kind == "half_batch":
        prog.step_fn = half_batch(prog.step_fn)
    return prog


def read(kind, prog, reference, seeds, cell, train):
    gb = cell.traffic["global_batch"]
    for seed in seeds:
        t0 = time.perf_counter()
        ds = prog.stream(seed)
        state, got = prog.checked_steps(seed, ds)
        del state
        want = reference.readings(seed, [ds.batch(s, gb) for s in range(
            train.CHECKED_STEPS)])
        checks = train.compare(got, want)
        left_out = sum(int((~keep).sum()) for keep in
                       train.ref.moved_rows(want.grad).values())
        print(json.dumps({
            "kind": kind, "seed": seed, "rows_left_out": left_out,
            **{k: v for k, (v, _) in checks.items()},
            "worst_at": {k: at for k, (_, at) in checks.items()},
            "losses": got.losses, "reference_losses": want.losses,
            "seconds": time.perf_counter() - t0}), flush=True)


KINDS = ("program", "bf16_compute", "bf16_operands", "control",
         "half_batch", "exchange")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--read", action="append", default=[],
                    metavar="KIND=SEEDS",
                    help=f"one of {', '.join(KINDS)}, and seeds such as "
                         f"101-112,120")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    reads = [spec.partition("=")[::2] for spec in args.read]
    unknown = [k for k, _ in reads if k not in KINDS]
    if unknown:
        raise SystemExit(f"unknown kinds {unknown}; known: {KINDS}")

    cell = harness.Cell.load(args.workload)
    if args.rehearse:
        cell = cell.rehearsal()
    devs = harness.take_devices(cell.chips, require_tpu=not args.rehearse)
    log = harness.Log(devs)
    train = harness.job(cell.traffic["job"])
    reference = train.Reference(cell, devs)
    from repro.runtime import pipeline

    for kind, seeds in reads:
        lax = pipeline.lax
        if kind == "exchange":
            pipeline.lax = exchange_shim()
        try:
            read(kind, stand_in(kind, cell, devs, log, train), reference,
                 seed_list(seeds), cell, train)
        finally:
            pipeline.lax = lax
    log(f"calibration done in {time.perf_counter() - T_START:.1f} s")


if __name__ == "__main__":
    main()
