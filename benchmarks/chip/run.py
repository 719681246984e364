"""Run one benchmark cell on the chips of this machine.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``; ``harness.py`` says which files
it is made of.  With ``--trace 0`` the last line of standard output is the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics, read
from a profiler trace of a few more steps after the window.  The numbers
``correct`` compares, each beside its limit, come last in that line and
are the last lines of standard error.

Without as many TPU chips as the cell asks for, it exits non-zero and
prints no result.  ``--rehearse`` runs the cell at the smoke sizes its
files give, on whatever JAX finds (``JAX_PLATFORMS=cpu``), prints the
result it would give to standard error, and still exits non-zero.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from benchmarks.chip import harness  # noqa: E402


def result_line(cell, out: dict, devs, traced: bool, peaks=None) -> dict:
    """The contract's last line, from a job's output."""
    d = devs[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devs), "memory_peak_bytes": out["memory_peak_bytes"]}
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    if traced:
        summary = out["trace"]
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_ns / 1e9
        inputs = dict(out["layer_inputs"], trace=summary, peaks=peaks)
        values = {m["name"]: harness.metric_reader(m["name"]).read(inputs)
                  for m in cell.per_layer}
    else:
        values = {m["name"]: out["end_to_end"][m["name"]]
                  for m in cell.end_to_end}
    checks = {name: {"value": v, "limit": cell.limits[name], "worst_at": at}
              for name, (v, at) in out["checks"].items()}
    correct = (out["failed"] == 0
               and all(c["value"] <= c["limit"] for c in checks.values()))
    line = {"correct": correct, "attempted": out["attempted"],
            "failed": out["failed"],
            "metrics": {k: {"value": v, "unit": units[k]}
                        for k, v in values.items() if v is not None},
            "device": device}
    if traced:
        line["breakdown"] = {"device_ops": out["trace"].top_ops(),
                             "idle_gaps": out["trace"].longest_gaps()}
    line["checks"] = checks
    return line


def measure(cell, seed: int, seconds: float, traced: bool,
            require_tpu: bool = True):
    """One run of ``cell``: returns ``(result line, devices, log)``."""
    devs = harness.take_devices(cell.chips, require_tpu=require_tpu)
    log = harness.Log(devs)
    peaks = harness.peaks(devs[0].device_kind) if traced else None
    out = harness.job(cell.traffic["job"]).run(
        cell, devs, log, seed=seed, seconds=seconds, traced=traced,
        t_start=T_START)
    return result_line(cell, out, devs, traced, peaks), devs, log


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="smoke sizes on any platform; still exits non-zero")
    args = ap.parse_args(argv)
    if args.seed < 0:
        raise SystemExit("--seed must be a non-negative whole number")

    cell = harness.Cell.load(args.workload)
    if args.rehearse:
        cell = cell.rehearsal()
    counters = harness.compile_counters()
    line, devs, log = measure(cell, args.seed, args.seconds,
                              bool(args.trace),
                              require_tpu=not args.rehearse)
    log(f"compile: backend {counters['compile_s']:.3f} s, persistent cache "
        f"hits {counters['cache_hits']} misses {counters['cache_misses']}")
    harness.print_checks(line["checks"], log)
    if args.rehearse or devs[0].platform != "tpu":
        print(json.dumps(line), file=sys.stderr)
        raise SystemExit("rehearsal: not a chip run, no result")
    bad = [k for k, m in line["metrics"].items()
           if not math.isfinite(m["value"])]
    if bad:
        raise SystemExit(f"non-finite metrics {bad}")
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
