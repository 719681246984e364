"""Measured on-device profiling CLI (paper §3.3).

Executes the *real* per-layer ``(tf, tb)`` sweeps
(``core.profiler.measure_layer_times``) for a model config on the local
host across a batch-size sweep, and serializes the result — raw time
tables plus device/cluster metadata and staleness fingerprints — to a
versioned JSON artifact the planner can consume instead of the analytic
FLOP model:

    PYTHONPATH=src python -m repro.launch.profile --quick -o prof.json
    PYTHONPATH=src python -m repro.launch.train --plan --profile prof.json

Under a multi-process JAX mesh every rank measures its own accelerator and
the sweeps are gathered to rank 0, which writes one device row per rank
(single-process runs just profile the host).  ``--replicate N`` tiles the
host's row into N virtual devices, emulating a homogeneous edge cluster
from one measurement so the planner can produce multi-stage plans on a
laptop — the paper's setting would run this CLI once per Jetson instead.

On a CPU host the measured numbers are CPU numbers; the point is the
pipeline (measure -> serialize -> plan -> lower -> execute), which is
hardware-agnostic.  See DESIGN.md §3 for the artifact schema and the
staleness rules ``launch.train`` applies before trusting an artifact.
"""

from __future__ import annotations

import argparse
import itertools
import os
import sys
import time


def _host_mem_bytes(default: float = 8e9) -> float:
    """Physical memory of this host (the planner's budget u_d)."""
    try:
        return float(os.sysconf("SC_PHYS_PAGES") * os.sysconf("SC_PAGE_SIZE"))
    except (ValueError, OSError, AttributeError):
        return default


def build_layer_fns(cfg, seq_len: int, key=None):
    """Per-layer callables matching ``LayerTable.from_model_config(cfg)``.

    Returns ``(layer_fns, make_input)`` for ``measure_layer_times``: one
    jittable ``x -> y`` per table entry (embed, the leading dense layers,
    each of the ``n_layers`` block layers, head), bound to freshly-initialized
    params.  Block layers
    reuse one period's params per pattern slot — timing is weight-value
    independent, so one init covers all periods.
    """
    import jax
    import jax.numpy as jnp

    from repro.models.blocks import apply_layer
    from repro.models.config import DENSE_LAYER
    from repro.models.model import _head_weight, embed_tokens, init_model

    if key is None:
        key = jax.random.PRNGKey(0)
    params = init_model(key, cfg)
    period0 = jax.tree.map(lambda x: x[0], params["periods"])

    def embed_fn(tokens):
        return embed_tokens(params, tokens, cfg)

    fns = [embed_fn]
    layers = [(DENSE_LAYER, lp) for lp in params.get("dense_prefix", ())]
    layers += [(cfg.pattern[li % len(cfg.pattern)],
                period0["layers"][li % len(cfg.pattern)])
               for li in range(cfg.n_layers)]
    for spec, lp in layers:

        def block_fn(x, lp=lp, spec=spec):
            B, S = x.shape[0], x.shape[1]
            positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32),
                                         (B, S))
            return apply_layer(lp, x, positions, cfg, spec)[0]

        fns.append(block_fn)

    head_w = _head_weight(params, cfg,
                          codebook=0 if cfg.n_codebooks > 1 else None)

    def head_fn(x):
        return x @ head_w

    fns.append(head_fn)

    def make_input(beta: int, li: int):
        if li == 0:            # embed consumes token ids
            shape = (beta, cfg.n_codebooks, seq_len) if cfg.n_codebooks > 1 \
                else (beta, seq_len)
            return jnp.zeros(shape, jnp.int32)
        return jnp.ones((beta, seq_len, cfg.d_model), cfg.cdtype) * 0.01

    return fns, make_input


_KV_GATHER_CALLS = itertools.count()


def gather_process_rows(tf, tb):
    """Gather every process's ``(n_batches, L)`` sweep into ``(D, ...)``.

    Primary path: ``multihost_utils.process_allgather`` (one jitted
    all-gather over the global mesh — what a real TPU/GPU edge mesh runs).
    The CPU backend hosts a multi-process *coordination* service but not
    multi-process XLA computations, so there the rows travel through the
    distributed KV store instead — same contract, control-plane transport
    (bit-exact: float64 lists survive the JSON round trip).  Exercised by
    ``repro.launch.profile_selftest`` (2 processes) in CI.
    """
    import jax
    import numpy as np

    n = jax.process_count()
    if n == 1:
        return np.asarray(tf)[None], np.asarray(tb)[None]
    from jax.experimental import multihost_utils
    try:
        return (np.asarray(multihost_utils.process_allgather(tf)),
                np.asarray(multihost_utils.process_allgather(tb)))
    except Exception as e:                      # noqa: BLE001
        if "Multiprocess computations" not in str(e):
            raise
    import json

    from jax._src import distributed

    client = distributed.global_state.client
    rank = jax.process_index()
    # keys and barrier ids are single-use in the coordination service —
    # suffix with a per-call counter so repeated measure_model calls in
    # one distributed run (several configs / seq_lens) keep working
    call = next(_KV_GATHER_CALLS)
    payload = json.dumps({"tf": np.asarray(tf).tolist(),
                          "tb": np.asarray(tb).tolist()})
    client.key_value_set(f"asteroid/profile_row/{call}/{rank}", payload)
    client.wait_at_barrier(f"asteroid_profile_gather/{call}", 120_000)
    rows = [json.loads(client.blocking_key_value_get(
        f"asteroid/profile_row/{call}/{r}", 120_000)) for r in range(n)]
    return (np.stack([np.asarray(r["tf"]) for r in rows]),
            np.stack([np.asarray(r["tb"]) for r in rows]))


def measure_model(cfg, seq_len: int, batch_sizes=(1, 2, 4), repeats: int = 3,
                  *, replicate: int = 1, mem_bytes: float | None = None,
                  bandwidth: float | None = None, seed: int = 0):
    """Profile ``cfg`` on the local host into a ``MeasuredProfile``.

    Runs the jitted per-layer sweep, gathers one device row per JAX process
    (``gather_process_rows`` — every rank receives every row), then
    tiles rows ``replicate`` times into virtual devices.  The effective
    FLOP rate at the largest measured batch is recorded per device so
    ``MeasuredProfile.cluster()`` yields the best analytic model of the
    same hardware.
    """
    import jax
    import numpy as np

    from repro.core.hardware import MBPS_1000
    from repro.core.profiler import (LayerTable, MeasuredProfile,
                                     config_fingerprint, device_fingerprint,
                                     measure_layer_times)

    table = LayerTable.from_model_config(cfg, seq_len)
    fns, make_input = build_layer_fns(cfg, seq_len, jax.random.PRNGKey(seed))
    assert len(fns) == table.L, (len(fns), table.L)
    batch_sizes = tuple(sorted(set(int(b) for b in batch_sizes)))
    t0 = time.perf_counter()
    tf, tb = measure_layer_times(fns, make_input, batch_sizes, repeats)
    elapsed = time.perf_counter() - t0

    plat = jax.local_devices()[0].platform
    tf, tb = gather_process_rows(tf, tb)             # (D, n_batches, L)
    if jax.process_count() > 1:
        names = [f"{plat}:{r}" for r in range(jax.process_count())]
    else:
        names = [f"{plat}:0"]

    if replicate > 1:
        tf = np.tile(tf, (replicate, 1, 1))
        tb = np.tile(tb, (replicate, 1, 1))
        names = [f"{n}/v{k}" for n in names for k in range(replicate)]
    # (D, n_batches, L)
    beta_max = batch_sizes[-1]
    est = tuple(float(table.flops(0, table.L) * beta_max /
                      max(tf[d, -1].sum(), 1e-12)) for d in range(len(names)))
    mem = mem_bytes if mem_bytes is not None else _host_mem_bytes()
    return MeasuredProfile(
        arch=cfg.name, seq_len=seq_len, batch_sizes=batch_sizes,
        layer_names=tuple(l.name for l in table.layers),
        tf=tf, tb=tb, device_names=tuple(names),
        config_hash=config_fingerprint(cfg, seq_len),
        device_hash=device_fingerprint(),
        mem_bytes=(float(mem),) * len(names), est_flops=est,
        bandwidth=float(bandwidth if bandwidth is not None else MBPS_1000),
        repeats=repeats,
        meta={"jax": jax.__version__,
              "python": sys.version.split()[0],
              "platform": plat,
              "measure_seconds": round(elapsed, 3),
              "created": time.strftime("%Y-%m-%dT%H:%M:%S%z")})


def main(argv=None) -> str:
    ap = argparse.ArgumentParser(
        description="measure per-layer (tf, tb) sweeps on the local host "
                    "and write a planner-consumable profile artifact")
    ap.add_argument("--arch", default="phi3-mini-3.8b")
    ap.add_argument("--smoke", action="store_true",
                    help="profile the reduced same-family config")
    ap.add_argument("--quick", action="store_true",
                    help="CI-sized run: --smoke, seq 64, batches 1,2,4, "
                         "1 repeat, 4 virtual devices")
    ap.add_argument("--seq", type=int, default=None,
                    help="sequence length (default 128; 64 under --quick)")
    ap.add_argument("--batches", default=None,
                    help="comma-separated batch sizes to sweep "
                         "(default 1,2,4,8; 1,2,4 under --quick)")
    ap.add_argument("--repeats", type=int, default=None,
                    help="timed repetitions per (layer, batch) after the "
                         "compile warm-up (default 3; 1 under --quick)")
    ap.add_argument("--replicate", type=int, default=None,
                    help="tile the host row into N virtual devices "
                         "(default 1; 4 under --quick)")
    ap.add_argument("--mem-gb", type=float, default=None,
                    help="override the per-device memory budget "
                         "(default: host physical memory)")
    ap.add_argument("--bw-mbps", type=float, default=None,
                    help="assumed D2D bandwidth between profiled devices "
                         "(default 1000)")
    ap.add_argument("-o", "--out", default="prof.json")
    args = ap.parse_args(argv)

    seq = args.seq if args.seq is not None else (64 if args.quick else 128)
    batches = tuple(int(b) for b in args.batches.split(",")) if args.batches \
        else ((1, 2, 4) if args.quick else (1, 2, 4, 8))
    repeats = args.repeats if args.repeats is not None else \
        (1 if args.quick else 3)
    replicate = args.replicate if args.replicate is not None else \
        (4 if args.quick else 1)

    from repro.configs import get_config, get_smoke_config
    from repro.core.profiler import save_profile

    smoke = args.smoke or args.quick
    cfg = get_smoke_config(args.arch) if smoke else get_config(args.arch)
    print(f"profiling {cfg.name} (smoke={smoke}) seq={seq} "
          f"batches={batches} repeats={repeats} replicate={replicate}")
    mp = measure_model(cfg, seq, batches, repeats, replicate=replicate,
                       mem_bytes=None if args.mem_gb is None
                       else args.mem_gb * 1e9,
                       bandwidth=None if args.bw_mbps is None
                       else args.bw_mbps * 1e6 / 8)
    import dataclasses
    mp = dataclasses.replace(mp, meta={**mp.meta, "arch_id": args.arch,
                                       "smoke": smoke})
    import jax
    if jax.process_index() != 0:
        return args.out          # rank 0 gathered every row and writes
    for li, name in enumerate(mp.layer_names):
        fwd = " ".join(f"{mp.tf[0, bi, li] * 1e3:8.3f}"
                       for bi in range(len(mp.batch_sizes)))
        bwd = " ".join(f"{mp.tb[0, bi, li] * 1e3:8.3f}"
                       for bi in range(len(mp.batch_sizes)))
        print(f"  {name:>10s}  fwd[ms] {fwd}   bwd[ms] {bwd}")
    save_profile(args.out, mp)
    print(f"profile ({mp.D} device rows x {len(mp.batch_sizes)} batches x "
          f"{mp.L} layers) -> {args.out}")
    return args.out


if __name__ == "__main__":
    main()
