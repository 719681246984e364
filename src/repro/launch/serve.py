"""Distributed serving launcher: batched autoregressive decode.

    PYTHONPATH=src python -m repro.launch.serve --arch phi3-mini-3.8b \
        --smoke --devices 8 --batch 8 --prompt-len 16 --gen 32

Continuous-batching mode (DESIGN.md §11): plan_serve picks the stage
split and the heterogeneous per-shard slot counts against a modeled
edge cluster, build_slot_serve_step lowers them onto the local mesh,
and an open-loop Poisson request stream is served through
ContinuousBatcher with slot-level admission control:

    PYTHONPATH=src python -m repro.launch.serve --arch phi3-mini-3.8b \
        --smoke --devices 8 --continuous --requests 12 --gen 16

``--devices N`` serves on the first N of ``jax.devices()`` (forcing N host
devices on the CPU backend); without it every device is used.
"""

import argparse

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.launch.devices import select_devices


def run_continuous(args, cfg, mesh) -> None:
    """Planner-driven continuous batching on the local mesh."""
    import time

    from repro.core.hardware import Cluster, JETSON_NX, JETSON_TX2, MBPS_100
    from repro.core.planner import plan_serve
    from repro.core.profiler import LayerTable, Profile
    from repro.distributed.sharding import named
    from repro.runtime.continuous import (ContinuousBatcher,
                                          engine_from_serve_step,
                                          poisson_requests, slot_rows)
    from repro.runtime.serve import build_slot_serve_step, serve_head_count
    from repro.runtime.train import prepare_params

    if cfg.n_codebooks > 1:
        raise SystemExit("--continuous drives scalar token streams; "
                         "multi-codebook archs are not supported")
    dp, model_axis = mesh.shape["data"], mesh.shape["model"]
    cache_len = args.prompt_len + args.gen

    # Plan against a modeled heterogeneous edge cluster (alternating fast
    # NX / slow TX2 shard blocks) so the slot split is visibly unbalanced;
    # max_batch caps the per-shard slot count to what the host can pad.
    devs = tuple((JETSON_NX if d % 2 == 0 else JETSON_TX2,) * model_axis
                 for d in range(dp))
    cluster = Cluster(sum(devs, ()), bandwidth=MBPS_100)
    table = LayerTable.from_model_config(cfg, seq_len=cache_len)
    prof = Profile.analytic(table, cluster, max_batch=args.max_slots)

    # modeled offered load: --util of the equal-split capacity, so the
    # greedy split has queueing pressure to plan against
    from repro.core.planner import (_price_serve_alloc, _serve_cuts,
                                    serve_stage_candidates)
    stage0 = serve_stage_candidates(model_axis, serve_head_count(cfg))[0]
    cuts0 = _serve_cuts(table.L, stage0)
    cap = 0.0
    for y in range(1, args.max_slots + 1):
        st, _, _ = _price_serve_alloc(prof, [y] * dp, stage=stage0,
                                      tp=model_axis // stage0, cuts=cuts0,
                                      seq_len=cache_len, arrival_rate=0.0,
                                      compress=None)
        cap = max(cap, dp * y / st if st > 0 else 0.0)
    plan = plan_serve(prof, args.util * cap, dp_shards=dp,
                      model_axis=model_axis, n_heads=serve_head_count(cfg),
                      cache_len=cache_len, seq_len=cache_len, arch=cfg.name)
    print(f"serve plan: stage={plan.stage} tp={plan.tp} "
          f"alloc={plan.shard_alloc} caps={plan.max_slots} "
          f"modeled p99={plan.predicted_p99 * 1e3:.2f}ms")

    ss = build_slot_serve_step(cfg, mesh, cache_len=cache_len,
                               shard_alloc=plan.shard_alloc,
                               stage=plan.stage)
    key = jax.random.PRNGKey(0)
    params = jax.jit(lambda k: prepare_params(k, cfg, ss.spec.plan),
                     out_shardings=named(ss.mesh, ss.param_specs))(key)
    engine = engine_from_serve_step(ss, params)

    B = ss.spec.batch_global
    zeros = jnp.zeros(B, jnp.int32)
    jax.device_get(engine(zeros, zeros, jnp.ones(B, bool)))   # compile
    t0 = time.perf_counter()
    jax.device_get(engine(zeros, zeros, jnp.zeros(B, bool)))
    step_s = time.perf_counter() - t0
    rate = args.rate or args.util * plan.slots / step_s
    print(f"engine step {step_s * 1e3:.1f}ms on this host -> offered load "
          f"{rate:.1f} tok/s ({args.util:.0%} of capacity)")

    reqs = poisson_requests(rate / args.gen,
                            horizon=args.requests * args.gen / rate,
                            n_tokens=args.gen, seed=0,
                            vocab=cfg.vocab_size)
    bat = ContinuousBatcher(engine, slots=slot_rows(plan.shard_alloc),
                            batch=B, cache_len=cache_len, seed=0)
    done = bat.run(reqs)
    lats = np.array([l for c in done for l in c.token_latencies])
    total = sum(len(c.tokens) for c in done)
    span = max(c.finish for c in done) - min(c.arrival for c in done)
    p50, p95, p99 = np.percentile(lats, [50, 95, 99])
    from repro.core.costmodel import serve_latency_quantile
    pred = [serve_latency_quantile(step_s, plan.slots, rate, p)
            for p in (0.5, 0.95, 0.99)]
    print(f"served {len(done)} requests / {total} tokens in {bat.steps} "
          f"steps: {total / span:.1f} tok/s")
    print(f"token latency p50/p95/p99 = {p50 * 1e3:.1f}/{p95 * 1e3:.1f}/"
          f"{p99 * 1e3:.1f} ms (predicted from measured step: "
          f"{pred[0] * 1e3:.1f}/{pred[1] * 1e3:.1f}/{pred[2] * 1e3:.1f} ms)")
    print("done")


def main(argv=None) -> dict | None:
    """Run the launcher on ``argv`` (default ``sys.argv[1:]``).

    Lockstep decode returns ``{"tok_s", "logits_finite", "tokens"}``: the
    decode rate, whether every step's logits were finite, and the
    ``(T, B)`` token array (prompt then samples)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="phi3-mini-3.8b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--devices", type=int, default=0)
    ap.add_argument("--n-layers", type=int, default=None,
                    help="cut the depth (widths stay the config's)")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.8)
    ap.add_argument("--seq-shard", action="store_true")
    ap.add_argument("--continuous", action="store_true",
                    help="planner-driven continuous batching "
                         "(plan_serve -> slot step -> Poisson stream)")
    ap.add_argument("--requests", type=int, default=12,
                    help="--continuous: requests in the Poisson trace")
    ap.add_argument("--rate", type=float, default=0.0,
                    help="--continuous: offered load (tokens/s); default "
                         "derives from the measured step time and --util")
    ap.add_argument("--util", type=float, default=0.6,
                    help="--continuous: target utilization for the "
                         "derived offered load")
    ap.add_argument("--max-slots", type=int, default=4,
                    help="--continuous: per-shard slot cap handed to the "
                         "planner as profile.max_batch")
    args = ap.parse_args(argv)

    from repro.configs import get_config, get_smoke_config
    from repro.distributed.sharding import named
    from repro.runtime.serve import build_serve_step, prepare_serve_states
    from repro.runtime.train import prepare_params

    cfg = (get_smoke_config(args.arch) if args.smoke else get_config(args.arch))
    cfg = cfg.replace(prefix_len=0, mtp_depth=0)
    if args.n_layers:
        cfg = cfg.replace(n_layers=args.n_layers)
    devs = select_devices(args.devices)
    n = len(devs)
    data_axis = max(1, n // 4)
    mesh = Mesh(np.array(devs).reshape(data_axis, n // data_axis),
                ("data", "model"))
    if args.continuous:
        run_continuous(args, cfg, mesh)
        return
    cache_len = args.prompt_len + args.gen
    ss = build_serve_step(cfg, mesh, batch_global=args.batch,
                          cache_len=cache_len, seq_shard=args.seq_shard)
    print(f"arch={cfg.name} serve plan: stage={ss.spec.plan.stage} "
          f"tp={ss.spec.plan.tp} cache={cache_len}")

    key = jax.random.PRNGKey(0)
    params = jax.jit(lambda k: prepare_params(k, cfg, ss.spec.plan),
                     out_shardings=named(ss.mesh, ss.param_specs))(key)
    states = jax.jit(lambda: prepare_serve_states(cfg, ss.spec.plan,
                                                  args.batch, cache_len),
                     out_shardings=named(ss.mesh, ss.state_specs))()

    rng = np.random.RandomState(0)
    shape = (args.batch, cfg.n_codebooks) if cfg.n_codebooks > 1 else (args.batch,)
    prompt = rng.randint(0, cfg.vocab_size,
                         size=(args.prompt_len, *shape)).astype(np.int32)

    import time
    seqs = [prompt[t] for t in range(args.prompt_len)]
    tok = jnp.asarray(prompt[0])
    t0 = time.perf_counter()
    skey = key
    n_bad = jnp.int32(0)
    for pos in range(cache_len - 1):
        logits, states = ss.step_fn(params, tok, jnp.int32(pos), states)
        n_bad = n_bad + jnp.sum(~jnp.isfinite(logits))
        if pos == 0:                      # the compile step is not timed
            jax.block_until_ready(logits)
            t1 = time.perf_counter()
        if pos + 1 < args.prompt_len:
            tok = jnp.asarray(prompt[pos + 1])
        else:
            skey = jax.random.fold_in(skey, pos)
            nxt = jax.random.categorical(
                skey, jnp.asarray(logits) / args.temperature, axis=-1)
            tok = nxt.astype(jnp.int32)
            seqs.append(np.asarray(tok))
    jax.block_until_ready(logits)
    t_end = time.perf_counter()
    # every step after the first decodes one token per row
    tok_s = args.batch * (cache_len - 2) / max(t_end - t1, 1e-9)
    finite = int(n_bad) == 0
    print(f"decoded {cache_len - 1} steps x batch {args.batch} "
          f"({args.gen} generated): first step {t1 - t0:.2f}s (compile), "
          f"then {tok_s:.1f} tok/s on {n} {devs[0].platform} device(s) "
          f"({devs[0].device_kind})")
    print(f"logits finite: {finite}")
    out = np.stack(seqs)  # (T, B) or (T, B, CB)
    print("sample sequence 0:", out[:, 0].reshape(out.shape[0], -1)[:, 0][:24], "...")
    print("done")
    return {"tok_s": tok_s, "logits_finite": finite, "tokens": out}


if __name__ == "__main__":
    main()
