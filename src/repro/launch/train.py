"""Distributed training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch phi3-mini-3.8b \
        --smoke --devices 8 --steps 50 --global-batch 16 --seq 128

``--devices N`` trains on the first N of ``jax.devices()``; on the CPU
backend it first forces N host devices, so the same command rehearses an
N-chip mesh on a CPU host.  Without it every device is used.  The mesh is
``(data, model)`` with ``--data-axis`` rows (default ``max(1, N // 4)``).
"""

import argparse

import numpy as np

import jax
from jax.sharding import Mesh

from repro.launch.devices import select_devices


def _steady_tok_s(args, n_compile: int, t0: float, t_warm, t_end: float):
    """FINAL steady-state rate, shared by the plain and session paths:
    tokens over the steps after the compile step(s) (n_compile jitted
    entry points: 1 sync, 2 bounded-staleness), or over the whole run
    when there were no post-compile steps to time."""
    tokens = args.global_batch * args.seq
    if t_warm is not None:
        return tokens * (args.steps - n_compile) / max(t_end - t_warm, 1e-9)
    return tokens * args.steps / max(t_end - t0, 1e-9)


def main(argv=None) -> dict:
    """Run the launcher on ``argv`` (default ``sys.argv[1:]``).

    Returns ``{"loss", "log", "params", "optimizer", "tok_s"}``: the final
    loss, the ``(step, loss, seconds per step)`` of every logged step (each
    timed after ``block_until_ready``), the final params, the optimizer
    with its learning-rate schedule, and the steady-state token rate."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None,
                    help="architecture id (default phi3-mini-3.8b, or the "
                         "--profile artifact's recorded arch)")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-friendly)")
    ap.add_argument("--devices", type=int, default=0)
    ap.add_argument("--data-axis", type=int, default=None)
    ap.add_argument("--stage", type=int, default=None)
    ap.add_argument("--n-micro", type=int, default=None)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--global-batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=None,
                    help="sequence length (default 128, or the --profile "
                         "artifact's recorded seq_len)")
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--d-model", type=int, default=None,
                    help="override width (e.g. scale to ~100M params)")
    ap.add_argument("--n-layers", type=int, default=None)
    ap.add_argument("--plan", action="store_true",
                    help="derive stage split / n_micro / K_p from the "
                         "Asteroid planner (Algorithm 2) and lower it")
    ap.add_argument("--no-offload", action="store_true",
                    help="disable Algorithm 1 Phase 2 (straggler workload "
                         "offloading) when planning — the Fig. 15a ablation")
    ap.add_argument("--force-offload", action="store_true",
                    help="always keep the Phase 2 allocation (default: "
                         "'auto' — keep it only when the planner predicts "
                         "a strict latency gain, since a heterogeneous "
                         "allocation pads every data shard to B_max)")
    ap.add_argument("--staleness", type=int, default=0, choices=(0, 1),
                    help="async 1F1B gradient staleness bound: 0 = "
                         "synchronous rounds, 1 = round r's gradients are "
                         "applied at the r+1 boundary so their AllReduce "
                         "overlaps round r+1 (DESIGN.md §8)")
    ap.add_argument("--double-buffer", action=argparse.BooleanOptionalAction,
                    default=None,
                    help="double-buffer stage-boundary sends (2-tick hop, "
                         "transfer of micro-batch m overlaps compute of "
                         "m+1); default: on when --staleness 1")
    ap.add_argument("--compress", default="none",
                    choices=("none", "int8", "fp8", "auto"),
                    help="quantize boundary activation/gradient transfers "
                         "and the gradient AllReduce (DESIGN.md §10); "
                         "'auto' (requires --plan) lets the planner keep "
                         "compression only when it prices strictly faster")
    ap.add_argument("--quant-tile", type=int, default=256,
                    help="elements per quantization tile (one f32 scale "
                         "per tile on the wire)")
    ap.add_argument("--bucket-mb", type=float, default=None,
                    help="bucket the gradient AllReduce into size-bounded "
                         "chunks (MiB of compressed wire bytes); implies "
                         "the bucketed gradient path even without "
                         "--compress")
    ap.add_argument("--error-feedback", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="carry the per-bucket quantization residual into "
                         "the next round's gradients (unbiased in the "
                         "telescoping-sum sense); only active with "
                         "--compress")
    ap.add_argument("--env", default="D", choices=[*"ABCD", "v5e"],
                    help="cluster the analytic profile describes for "
                         "--plan: a paper edge environment A-D, or 'v5e' "
                         "(one TPU v5e chip per mesh device, ICI links); "
                         "ignored when a valid --profile artifact is given")
    ap.add_argument("--bandwidth", type=float, default=None, metavar="MBPS",
                    help="override the analytic environment's D2D link "
                         "bandwidth (megabits/s; default: the env preset's)")
    ap.add_argument("--profile", default=None, metavar="PATH",
                    help="measured profile artifact from "
                         "repro.launch.profile; the planner/lowering/"
                         "simulator run on its measured (tf, tb) tables, "
                         "falling back to the analytic model with a warning "
                         "if the artifact is stale or incompatible")
    ap.add_argument("--events", default=None, metavar="SCHEDULE",
                    help="membership event schedule, comma-separated "
                         "'kind@step[:arg]' entries, e.g. "
                         "'join@40:dev.json,drain@80:2'.  Kinds: fail/"
                         "drain/evict take a cluster rank (default: last "
                         "stage's lead device); join takes a device preset "
                         "(nano/tx2/nx/a100/v5e, default nx), a device-spec "
                         "JSON file ({name, mem_bytes, flops, ...}), or a "
                         "repro.launch.profile artifact measured on the "
                         "joining device (its sweep prices the admission). "
                         "Requires --plan")
    ap.add_argument("--hysteresis", type=float, default=None,
                    help="admission hysteresis margin for join events "
                         "(default: replay.ADMISSION_HYSTERESIS)")
    ap.add_argument("--fail-at", type=int, default=None,
                    help="sugar for --events 'fail@STEP[:--fail-rank]': "
                         "kill a rank before this step and recover through "
                         "the live replay session (requires --plan)")
    ap.add_argument("--fail-rank", type=int, default=None,
                    help="edge-cluster rank to kill (default: last stage's "
                         "lead device)")
    ap.add_argument("--backup-every", type=int, default=5,
                    help="stage-replication cadence in steps (with --events)")
    ap.add_argument("--portfolio", type=int, default=0, metavar="K",
                    help="closed-loop portfolio planning (DESIGN.md §12): "
                         "enumerate every strategy family, give the top-K "
                         "finalists a live probation window each, and "
                         "install the measured winner before training. "
                         "Requires --plan")
    ap.add_argument("--probation-rounds", type=int, default=2, metavar="N",
                    help="timed rounds per finalist in a portfolio "
                         "probation (plus one warmup round that the robust "
                         "stat trims)")
    ap.add_argument("--drift-threshold", type=float, default=None,
                    help="arm the portfolio drift watchdog: re-open the "
                         "auction when the EWMA of observed/predicted round "
                         "latency drifts more than this fraction from its "
                         "baseline (default: off — probe once, keep the "
                         "winner)")
    args = ap.parse_args(argv)
    events = _parse_events(args.events)
    if args.fail_at is not None:     # old flags kept as sugar
        arg = "" if args.fail_rank is None else str(args.fail_rank)
        events.append((args.fail_at, "fail", arg))
    events.sort(key=lambda e: e[0])
    if events and not args.plan:
        raise SystemExit("--events/--fail-at require --plan (the membership "
                         "session recovers by re-lowering a planner Plan)")
    if args.profile and not args.plan:
        raise SystemExit("--profile requires --plan (a measured profile "
                         "only feeds the planner)")
    if args.compress == "auto" and not args.plan:
        raise SystemExit("--compress auto requires --plan (the planner "
                         "prices the compressed vs raw wire)")
    if args.portfolio and not args.plan:
        raise SystemExit("--portfolio requires --plan (the auction probes "
                         "re-lowered planner Plans)")

    from repro import checkpoint
    from repro.configs import get_config, get_smoke_config
    from repro.data import SyntheticLM
    from repro.models.frontend import frontend_dim
    from repro.optim import AdamW, cosine_schedule
    from repro.runtime.pipeline import slot_counts
    from repro.runtime.train import build_train_step, init_train_state

    # a --profile artifact supplies the model/seq it was measured for;
    # explicit flags still win (a mismatch then falls back to analytic)
    measured = None
    if args.profile:
        from repro.core.profiler import load_profile
        measured = load_profile(args.profile)
        if args.arch is None and "arch_id" in measured.meta:
            args.arch = measured.meta["arch_id"]
        if args.seq is None:
            args.seq = measured.seq_len
        if not args.smoke and measured.meta.get("smoke"):
            print(f"adopting --smoke from profile artifact {args.profile}")
            args.smoke = True
    args.arch = args.arch or "phi3-mini-3.8b"
    args.seq = args.seq or 128

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    overrides = {}
    if args.d_model:
        overrides["d_model"] = args.d_model
    if args.n_layers:
        overrides["n_layers"] = args.n_layers
    if overrides:
        cfg = cfg.replace(**overrides)

    devs = select_devices(args.devices)
    n = len(devs)
    data_axis = args.data_axis or max(1, n // 4)
    model_axis = n // data_axis
    mesh = Mesh(np.array(devs).reshape(data_axis, model_axis),
                ("data", "model"))
    print(f"arch={cfg.name} params~{cfg.param_count()/1e6:.1f}M "
          f"mesh=(data={data_axis}, model={model_axis})")

    opt = AdamW(lr=cosine_schedule(args.lr, warmup=min(20, args.steps // 5),
                                   total=args.steps))
    if args.plan:
        from repro.core.hardware import ENVS, env_v5e
        from repro.core.lowering import plan_to_train_step
        from repro.core.planner import plan_hpp
        from repro.core.profiler import (LayerTable, Profile,
                                         resolve_profile)

        table = LayerTable.from_model_config(cfg, args.seq)
        max_batch = max(args.global_batch, 1)
        prof = resolve_profile(measured, cfg, args.seq, table, max_batch,
                               label=f"measured profile {args.profile}",
                               fallback_note=f" (env {args.env})")
        if prof is not None:
            print(f"profile=measured({args.profile}, "
                  f"{len(prof.cluster.devices)} devices, "
                  f"batches<={max(measured.batch_sizes)} measured)")
        else:
            cluster = (env_v5e(n) if args.env == "v5e"
                       else ENVS[args.env]())
            if args.bandwidth:
                from repro.core.hardware import Cluster
                cluster = Cluster(cluster.devices, args.bandwidth * 1e6 / 8)
            prof = Profile.analytic(table, cluster.sorted_by_memory(),
                                    max_batch=max_batch)
            print(f"profile=analytic(env {args.env}"
                  + (f", {args.bandwidth:g} Mbps" if args.bandwidth else "")
                  + ")")
        n_periods = cfg.n_layers // len(cfg.pattern)
        divisors = {d for d in range(1, model_axis + 1)
                    if model_axis % d == 0 and d <= n_periods}
        if args.n_micro:
            if args.global_batch % args.n_micro:
                raise SystemExit(f"--n-micro {args.n_micro} must divide "
                                 f"--global-batch {args.global_batch}")
            mb = args.global_batch // args.n_micro
        else:
            m = next(m for m in (4, 2, 1) if args.global_batch % m == 0)
            mb = args.global_batch // m
        if args.no_offload:
            intra_opt = False
        elif args.force_offload:
            intra_opt = True
        else:
            intra_opt = "auto"
        from repro.core.costmodel import CompressionConfig
        if args.compress == "auto":
            plan_compress = "auto"
        elif args.compress != "none":
            plan_compress = CompressionConfig(
                fmt=args.compress, tile=args.quant_tile,
                bucket_mb=args.bucket_mb,
                error_feedback=args.error_feedback)
        else:
            plan_compress = None
        plan = plan_hpp(prof, args.global_batch, mb, arch=cfg.name,
                        allowed_stages=divisors, intra_opt=intra_opt,
                        staleness=args.staleness, compress=plan_compress)
        # the runtime executes whatever the (possibly 'auto') plan chose
        run_compress = plan.compress.fmt if plan.compress else "none"
        compress_kw = dict(compress=run_compress,
                           quant_tile=args.quant_tile,
                           bucket_mb=args.bucket_mb,
                           error_feedback=args.error_feedback)
        if events or args.portfolio:
            from repro.runtime.session import PipelineSession
            watchdog = None
            if args.portfolio and args.drift_threshold is not None:
                from repro.core.portfolio import DriftWatchdog
                watchdog = DriftWatchdog(threshold=args.drift_threshold)
            session = PipelineSession(cfg, mesh, plan, prof, optimizer=opt,
                                      backup_every=args.backup_every,
                                      portfolio_k=args.portfolio,
                                      probation_window=args.probation_rounds,
                                      drift_watchdog=watchdog,
                                      staleness=args.staleness,
                                      double_buffer=args.double_buffer,
                                      **compress_kw)
            lowered = session.lowered
            print(f"asteroid plan: {lowered.stage} stages periods="
                  f"{lowered.stage_periods} (planner "
                  f"{lowered.planner_periods}) M={lowered.n_micro} "
                  f"K_p={lowered.warmup} predicted latency {plan.latency:.3f}s")
            return {"loss": _run_session(session, cfg, args, events)}
        ts, lowered = plan_to_train_step(plan, prof, cfg, mesh, optimizer=opt,
                                         staleness=args.staleness,
                                         double_buffer=args.double_buffer,
                                         **compress_kw)
        print(f"asteroid plan: {lowered.stage} stages periods="
              f"{lowered.stage_periods} (planner {lowered.planner_periods}) "
              f"M={lowered.n_micro} "
              f"K_p={lowered.warmup} alloc={lowered.micro_alloc} "
              f"predicted latency {plan.latency:.3f}s")
    else:
        ts = build_train_step(cfg, mesh, global_batch=args.global_batch,
                              stage=args.stage, n_micro=args.n_micro,
                              optimizer=opt, staleness=args.staleness,
                              double_buffer=args.double_buffer,
                              compress=args.compress,
                              quant_tile=args.quant_tile,
                              bucket_mb=args.bucket_mb,
                              error_feedback=args.error_feedback)
    real, computed = slot_counts(ts.spec)
    print(f"plan: stage={ts.spec.plan.stage} tp={ts.spec.plan.tp} "
          f"M={ts.spec.n_micro} slots {real}/{computed} shard_alloc="
          f"{ts.spec.shard_alloc or 'uniform'} "
          f"staleness={ts.spec.staleness} "
          f"double_buffer={ts.spec.double_buffer} "
          f"compress={ts.spec.compress}"
          + (f" bucket_mb={ts.spec.bucket_mb:g}" if ts.spec.bucket_mb else "")
          + (" ef" if ts.spec.bucketed and ts.spec.compress != "none"
             and ts.spec.error_feedback else ""))

    key = jax.random.PRNGKey(0)
    params, opt_state = init_train_state(key, ts, opt)
    ds = SyntheticLM(cfg.vocab_size, args.seq, n_codebooks=cfg.n_codebooks,
                     prefix_len=cfg.prefix_len, prefix_dim=frontend_dim(cfg))

    import time
    t0 = time.perf_counter()
    t_warm = None
    loss = float("nan")
    grad_buf = None
    bucketed = ts.spec.bucketed
    ef = ts.init_ef() if bucketed else None
    # steady state starts once every jitted entry point has compiled: the
    # sync path compiles step_fn at step 0; the bounded-staleness path
    # compiles grad_fn (first round) at step 0 and async_step_fn at step 1
    n_compile = 2 if ts.spec.staleness >= 1 else 1
    log = []
    t_prev, prev_step = t0, -1
    # The loop rebinds params and optimizer state every step, so the step
    # writes its outputs over their buffers; otherwise both are live twice.
    step_fn = jax.jit(ts.step_fn, donate_argnums=(0, 1))
    async_step_fn = (jax.jit(ts.async_step_fn, donate_argnums=(0, 1, 2))
                     if ts.spec.staleness >= 1 else None)
    for step in range(args.steps):
        batch = ts.shard_batch(ds.batch(step, args.global_batch))
        if ts.spec.staleness >= 1:
            if grad_buf is None:
                # first bounded-staleness round: gradients only, no update
                # (keeps the optimizer/schedule step count equal to sync)
                if bucketed:
                    (loss, metrics), grad_buf, ef = \
                        ts.grad_fn(params, batch, ef)
                else:
                    (loss, metrics), grad_buf = ts.grad_fn(params, batch)
            elif bucketed:
                params, opt_state, grad_buf, ef, loss, metrics = \
                    async_step_fn(params, opt_state, grad_buf, ef, batch)
            else:
                params, opt_state, grad_buf, loss, metrics = \
                    async_step_fn(params, opt_state, grad_buf, batch)
        elif bucketed:
            params, opt_state, ef, loss, metrics = \
                step_fn(params, opt_state, ef, batch)
        else:
            params, opt_state, loss, metrics = step_fn(params, opt_state,
                                                       batch)
        if step == n_compile - 1 and args.steps > n_compile:
            jax.block_until_ready(params)
            t_warm = time.perf_counter()      # exclude compile from FINAL
        if step % args.log_every == 0 or step == args.steps - 1:
            jax.block_until_ready((params, loss))
            now = time.perf_counter()
            step_s = (now - t_prev) / (step - prev_step)
            t_prev, prev_step = now, step
            log.append((step, float(loss), step_s))
            tput = args.global_batch * args.seq * (step + 1) / (now - t0)
            print(f"step {step:5d} loss {float(loss):.4f} "
                  f"ce {float(metrics['ce']):.4f} step {step_s:.3f}s "
                  f"tok/s {tput:,.0f}")
    jax.block_until_ready(params)
    t_end = time.perf_counter()          # before the flush: its one-off jit
    if grad_buf is not None:             # compile must not bias FINAL
        # staleness barrier: apply the final in-flight gradient round
        params, opt_state = ts.flush_fn(params, opt_state, grad_buf)
        jax.block_until_ready(params)
    steady = _steady_tok_s(args, n_compile, t0, t_warm, t_end)
    if args.checkpoint_dir:
        checkpoint.save(args.checkpoint_dir, "final", params)
        print(f"checkpoint saved to {args.checkpoint_dir}")
    print(f"FINAL tok_s={steady:.1f} loss={float(loss):.4f}")
    print("done")
    return {"loss": float(loss), "log": log, "params": params,
            "optimizer": opt, "tok_s": steady}


def _parse_events(spec: str | None) -> list:
    """Parse a ``--events`` schedule into ``(step, kind, arg)`` triples."""
    events = []
    if not spec:
        return events
    for entry in spec.split(","):
        entry = entry.strip()
        if not entry:
            continue
        head, _, arg = entry.partition(":")
        kind, at, step = head.partition("@")
        kind = kind.strip().lower()
        if kind not in ("fail", "join", "drain", "evict") or not at:
            raise SystemExit(f"--events entry {entry!r} is not "
                             "'kind@step[:arg]' with kind in "
                             "fail/join/drain/evict")
        try:
            events.append((int(step), kind, arg.strip()))
        except ValueError:
            raise SystemExit(f"--events entry {entry!r}: step {step!r} is "
                             "not an integer")
    return events


def _resolve_join(arg: str):
    """Resolve a join event's argument to ``(device, arrival_sweep)``.

    A preset name or device-spec JSON prices the newcomer analytically;
    a ``repro.launch.profile`` artifact supplies its measured on-arrival
    sweep (the device identity then comes from the sweep itself)."""
    import json

    from repro.core.hardware import (A100, JETSON_NANO, JETSON_NX,
                                     JETSON_TX2, TPU_V5E, DeviceProfile)
    presets = {"nano": JETSON_NANO, "tx2": JETSON_TX2, "nx": JETSON_NX,
               "a100": A100, "v5e": TPU_V5E}
    if not arg:
        return JETSON_NX, None
    if arg.lower() in presets:
        return presets[arg.lower()], None
    with open(arg) as f:
        doc = json.load(f)
    if "batch_sizes" in doc and "tf" in doc:     # a measured sweep artifact
        from repro.core.profiler import load_profile
        return None, load_profile(arg)
    try:
        dev = DeviceProfile(
            name=doc.get("name", "custom"),
            mem_bytes=float(doc["mem_bytes"]), flops=float(doc["flops"]),
            **{k: doc[k] for k in ("sat_batch", "sat_flops", "overhead")
               if k in doc})
    except KeyError as e:
        raise SystemExit(f"join device spec {arg} is missing {e} (need at "
                         "least name/mem_bytes/flops, or pass a "
                         "repro.launch.profile artifact)")
    return dev, None


def _apply_event(session, kind: str, arg: str, args) -> None:
    """Fire one membership event on the live session and report it."""
    from repro.core.replay import ADMISSION_HYSTERESIS

    if kind == "join":
        device, arrival = _resolve_join(arg)
        out = session.admit(device, arrival=arrival,
                            hysteresis=(args.hysteresis
                                        if args.hysteresis is not None
                                        else ADMISSION_HYSTERESIS))
        dec = out.decision
        if not out.accepted:
            print(f"  join rejected ({dec.reason})")
            return
        rep = out.report
        print(f"  joined ({dec.reason}): replan {rep.replan_s * 1e3:.1f}ms "
              f"migrate {rep.migration_s:.2f}s replicate "
              f"{rep.replicate_s:.2f}s | {dec.incumbent_latency:.3f}s -> "
              f"{dec.candidate_latency:.3f}s/round | new stages "
              f"{[(st.layers, st.group) for st in session.plan.stages]}")
        return
    rank = int(arg) if arg else session.plan.stages[-1].group[0]
    if kind == "fail":
        print(f"  killing rank {rank}")
        session.fail(rank)      # detected + recovered inside the next step
        return
    out = session.drain(rank) if kind == "drain" else session.evict(rank)
    rep = out.report
    print(f"  {kind} rank {rank} ({out.mode}"
          f"{', overlapped' if rep.overlapped else ''}): replan "
          f"{rep.replan_s * 1e3:.1f}ms migrate {rep.migration_s:.2f}s "
          f"stall {out.stall_s:.3f}s | new stages "
          f"{[(st.layers, st.group) for st in session.plan.stages]}")


def _run_session(session, cfg, args, events) -> float:
    """Drive a live membership session: train through the scheduled
    join/drain/evict/fail events without restarting."""
    import time

    from repro.data import SyntheticLM
    from repro.models.frontend import frontend_dim

    key = jax.random.PRNGKey(0)
    session.init(key)
    ds = SyntheticLM(cfg.vocab_size, args.seq, n_codebooks=cfg.n_codebooks,
                     prefix_len=cfg.prefix_len, prefix_dim=frontend_dim(cfg))
    if getattr(args, "portfolio", 0):
        # opening auction (DESIGN.md §12): probe the top-K finalists on the
        # live mesh before the first training step; the probation is
        # invisible to training state — pinned by the bit-identity line the
        # portfolio-smoke CI job greps for
        import json

        before = session.canonical_leaves()
        report = session.probe_portfolio(ds.batch(0, args.global_batch),
                                         k=args.portfolio,
                                         window=args.probation_rounds)
        after = session.canonical_leaves()
        identical = all(
            np.array_equal(a, b)
            for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(after)))
        w, f = report.winner, report.first_choice
        print(f"portfolio: winner installed {w.family} measured "
              f"{w.measured_s * 1e3:.2f}ms/round (analytic first choice "
              f"{f.family} measured {f.measured_s * 1e3:.2f}ms; "
              f"{len(report.results)} finalists of {report.n_candidates} "
              f"candidates, {report.window}-round probation)")
        print(f"portfolio: probation state bit-identical: {identical}")
        rec = dict(report.to_record(), bit_identical=identical)
        print("PORTFOLIO " + json.dumps(rec))
    loss = float("nan")
    seen_recoveries = 0
    pending = sorted(events, key=lambda e: e[0])
    sim_busy = 0.0          # edge-cluster round time under the deployed plan
    t0 = time.perf_counter()
    t_warm = None
    # same compile accounting as the main path: the staleness path has two
    # jitted entry points (first-round grad_fn, then async_step_fn); the
    # spec is read AFTER any opening auction — the installed winner's
    # semantics decide which entry points exist
    n_compile = 2 if session.ts.spec.staleness >= 1 else 1
    for step in range(args.steps):
        while pending and pending[0][0] <= step:
            _, kind, arg = pending.pop(0)
            print(f"step {step}: {kind} event")
            _apply_event(session, kind, arg, args)
        loss, metrics = session.step(ds.batch(step, args.global_batch))
        sim_busy += session.plan.latency
        if step == n_compile - 1 and args.steps > n_compile:
            jax.block_until_ready(session.params)
            t_warm = time.perf_counter()      # exclude compile from FINAL
        if len(session.recoveries) > seen_recoveries:
            seen_recoveries = len(session.recoveries)
            out = session.recoveries[-1]
            rep = out.report
            print(f"  recovered ({out.mode}): detect {rep.detection_s:.2f}s "
                  f"replan {rep.replan_s * 1e3:.1f}ms migrate "
                  f"{rep.migration_s:.2f}s restore {rep.restore_s:.2f}s | "
                  f"moved periods {out.migration.moved_periods} restored "
                  f"{out.restored_periods} | new stages "
                  f"{[(st.layers, st.group) for st in session.plan.stages]}")
        if step % args.log_every == 0 or step == args.steps - 1:
            dt = time.perf_counter() - t0
            tput = args.global_batch * args.seq * (step + 1) / dt
            print(f"step {step:5d} loss {loss:.4f} "
                  f"ce {float(metrics['ce']):.4f} tok/s {tput:,.0f}")
    jax.block_until_ready(session.params)
    t_end = time.perf_counter()     # flush compile must not bias FINAL
    session.flush_gradients()       # staleness barrier at end of training
    jax.block_until_ready(session.params)
    if args.checkpoint_dir:
        from repro import checkpoint
        checkpoint.save(args.checkpoint_dir, "final", session.params)
        print(f"checkpoint saved to {args.checkpoint_dir}")
    # same steady-state definition as the main path (shared helper), so
    # FINAL lines stay comparable across the two paths
    tput = _steady_tok_s(args, n_compile, t0, t_warm, t_end)
    # throughput on the simulated edge-cluster clock: per-round latency of
    # whichever plan was deployed at each step, plus the stall every
    # membership transition charged — the metric the churn benchmark tracks
    stalls = sum(o.stall_s for o in session.memberships)
    sim_tput = args.global_batch * args.seq * args.steps / max(
        sim_busy + stalls, 1e-9)
    print(f"FINAL sim_tok_s={sim_tput:.1f} (rounds {sim_busy:.2f}s + "
          f"membership stalls {stalls:.3f}s)")
    print(f"FINAL tok_s={tput:.1f} loss={loss:.4f}")
    print("done")
    return loss


if __name__ == "__main__":
    main()
