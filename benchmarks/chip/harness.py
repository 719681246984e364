"""Everything a job shares: the spec files, the chips, the output lines.

A cell is found by its name in ``BENCHMARK.json``; its configuration,
traffic mix, limits and per-layer metrics are files of their own under
this directory, found by the names given there:

* ``configs/<config>.json``: the model as run, its source and cut; its
  ``reference`` names the model family, whose plain reference is
  ``reference/<family>.py`` and whose FLOP count is ``flops/<family>.py``;
* ``traffic/<traffic>.json``: the job (``jobs/<job>.py``) and its sizes;
* ``limits/<cell>.json``: the limit of each number ``correct`` compares;
* ``metrics/<metric>.py``: the reader of one per-layer metric;
* ``peaks.json``: the chip's peaks, by ``device_kind``.
"""

from __future__ import annotations

import dataclasses
import importlib
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with the files it names."""

    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list

    @classmethod
    def load(cls, name: str, bench: dict | None = None) -> "Cell":
        bench = bench or load_json(ROOT / "BENCHMARK.json")
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise SystemExit(f"unknown workload {name!r}; known: "
                             f"{sorted(cells)}")
        w = cells[name]

        def applies(metric):
            return name in metric.get("workloads", [name])

        return cls(name=name, chips=w["chips"],
                   config=load_json(HERE / "configs" / f"{w['config']}.json"),
                   traffic=load_json(HERE / "traffic" / f"{w['traffic']}.json"),
                   limits=load_json(HERE / "limits" / f"{name}.json"),
                   end_to_end=[m for m in bench["end_to_end"] if applies(m)],
                   per_layer=[m for m in bench["per_layer"] if applies(m)])

    def rehearsal(self) -> "Cell":
        """The same cell at the smoke sizes its files give for a CPU run."""
        cfg = merge(self.config, {"model": self.config["rehearsal"]})
        return dataclasses.replace(
            self, config=cfg,
            traffic=merge(self.traffic, self.traffic["rehearsal"]))


def merge(base: dict, over: dict) -> dict:
    """``base`` with ``over``'s values, nested dicts merged key by key."""
    out = dict(base)
    for k, v in over.items():
        out[k] = (merge(base[k], v) if isinstance(v, dict)
                  and isinstance(base.get(k), dict) else v)
    return out


def job(name: str):
    return importlib.import_module(f"benchmarks.chip.jobs.{name}")


def metric_reader(name: str):
    return importlib.import_module(f"benchmarks.chip.metrics.{name}")


def peaks(device_kind: str) -> dict:
    table = load_json(HERE / "peaks.json")
    if device_kind not in table["devices"]:
        raise SystemExit(f"no peaks for device kind {device_kind!r} in "
                         f"peaks.json (known: {sorted(table['devices'])})")
    return table["devices"][device_kind]


class Log:
    """Output lines, each naming the platform, device kind and count."""

    def __init__(self, devices):
        d = devices[0]
        self.tag = f"[{d.platform} {d.device_kind} x{len(devices)}]"

    def __call__(self, msg: str) -> None:
        print(f"{self.tag} {msg}", flush=True)


def compile_counters() -> dict:
    """Backend compile seconds and persistent-cache hits and misses, summed
    over the process from JAX's monitoring events."""
    import jax
    from jax._src import dispatch

    c = {"compile_s": 0.0, "cache_hits": 0, "cache_misses": 0}

    def on_duration(event, secs, **_):
        if event == dispatch.BACKEND_COMPILE_EVENT:
            c["compile_s"] += secs

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            c["cache_hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            c["cache_misses"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    return c


def take_devices(n: int, require_tpu: bool = True) -> list:
    """The first ``n`` devices, through the program's own selection (which
    also places its compile cache inside the checkout).  Without ``n`` TPU
    chips this exits non-zero."""
    import jax

    from repro.launch.devices import select_devices

    # every program goes to the persistent cache, however fast it compiled,
    # so that a warm run compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devs = select_devices(n)
    if require_tpu and devs[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX finds {len(jax.devices())} "
                         f"{devs[0].platform} devices")
    return devs


def print_checks(checks: dict, log: Log) -> None:
    """Each compared number beside its limit, as the last lines of stderr."""
    for name, c in checks.items():
        print(f"{log.tag} check {name} {c['value']!r} limit {c['limit']!r} "
              f"({'ok' if c['value'] <= c['limit'] else 'FAILED'})",
              file=sys.stderr, flush=True)
