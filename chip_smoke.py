"""Smoke run of the main path on TPU chips, through the launchers users call.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the pipeline across four chips

One chip: planned training of phi3-mini-3.8b at its published widths with
the depth cut (``launch/train.py --plan``), the losses of its first two
steps against the plain model's, with one plain gradient and optimizer step
between them, at the highest matmul precision, and lockstep decode
(``launch/serve.py``).  Four chips: the planned training path on a
``(data=1, model=4)`` mesh, params checked on every chip, and the same loss
parity.  Weights are random, made from a fixed seed; the data is the repo's
synthetic LM stream.

Everything runs in this one process, because a chip belongs to one process
at a time.  The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
A failed phase raises, and the script exits non-zero without that line; it
also exits non-zero, before any work, when JAX finds no TPU.

``--tiny`` rehearses every phase on the CPU at smoke widths
(``JAX_PLATFORMS=cpu python chip_smoke.py --tiny``) and then still fails the
device check: a CPU run is never reported as a chip run.
"""

import argparse
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.launch.devices import select_devices  # noqa: E402

ARCH = "phi3-mini-3.8b"
# Depth for each chip count, and micro-batches per step; the widths stay
# published.  The launcher donates params and Adam state to the step.
# Compiling the step for a described v5e (16 GiB, 15.75 GiB for programs)
# settled these: see DEPTH_WHY.
DEPTH = {1: 4, 4: 8}
N_MICRO = 8
DEPTH_WHY = {
    1: "4 layers in micro-batches of 1 compile to a 15.46 GB peak; in "
       "micro-batches of 2 they need 16.03 GiB",
    4: "the plan splits the 8 periods 3|2|2|1 and every stage holds slots "
       "for the largest share: in micro-batches of 1 that compiles to a "
       "12.97 GB peak per chip",
}
SEQ, BATCH, STEPS = 2048, 8, 4
TINY_SEQ = 64
# Step-0 and step-1 losses against the reference, by platform.  The
# runtime multiplies at the config's default precision, the reference at
# "highest".  On a TPU the default is single bf16 passes: at these widths
# and seq 2048, running the whole model in bf16 on the CPU moved the loss
# by at most 2.4e-4, and a TPU v5e's first step differed from the
# reference by 9.5e-6.  On the CPU the default is full f32 and the two
# agree to about 1e-6.  Each run also computes two planted faults, which
# must fall outside the tolerance, so each run shows that the check can
# see them: the last layer removed (step 0), and the update of every layer
# but the last withheld, as when the backward pass stops at a stage
# boundary (step 1).  A gradient wrong only by a constant factor is not
# among them: global-norm clipping and Adam both divide it out.
LOSS_TOL = {"tpu": 1e-3, "cpu": 1e-5}


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or non-finite result."""


def _compile_counters() -> dict:
    """Backend compile seconds and persistent-cache hits/misses, summed
    over the process from JAX's monitoring events."""
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


def _peak_memory(devs) -> str:
    out = []
    for d in devs:
        stats = d.memory_stats() or {}
        peak = stats.get("peak_bytes_in_use")
        out.append(f"{d.id}:" + ("not reported" if peak is None
                                 else f"{peak / 1e9:.2f}GB"))
    return " ".join(out)


def train_phase(n_chips: int, depth: int, seq: int, tiny: bool) -> dict:
    from repro.launch import train

    argv = ["--plan", "--env", "v5e", "--arch", ARCH,
            "--n-layers", str(depth), "--seq", str(seq),
            "--global-batch", str(BATCH), "--n-micro", str(N_MICRO),
            "--steps", str(STEPS),
            "--devices", str(n_chips), "--log-every", "1"]
    if tiny:
        argv.append("--smoke")
    print(f"[train] launch.train {' '.join(argv)}", flush=True)
    res = train.main(argv)
    losses = [loss for _, loss, _ in res["log"]]
    if len(losses) != STEPS or not all(math.isfinite(x) for x in losses):
        raise SmokeFailure(f"train losses {losses}: expected {STEPS} "
                           "finite values")
    times = [s for _, _, s in res["log"]]
    print(f"[train] losses {losses}", flush=True)
    print(f"[train] step seconds {times} (step 0 includes compile)",
          flush=True)
    return res


def check_params_on_chips(params, devs) -> None:
    """Every chip must hold its own slice of the period stack."""
    per_dev = {d: 0 for d in devs}
    rows = {d: set() for d in devs}
    for leaf in jax.tree.leaves(params["periods"]):
        for sh in leaf.addressable_shards:
            per_dev[sh.device] += sh.data.nbytes
            rows[sh.device].add(sh.index[0].start or 0)
    total = {d: 0 for d in devs}
    for leaf in jax.tree.leaves(params):
        for sh in leaf.addressable_shards:
            total[sh.device] += sh.data.nbytes
    print("[train] param bytes per chip: " + " ".join(
        f"{d.id}:{total[d] / 1e9:.2f}GB(periods {per_dev[d] / 1e9:.2f}GB)"
        for d in devs), flush=True)
    empty = [d.id for d in devs if per_dev[d] == 0]
    if empty:
        raise SmokeFailure(f"chips {empty} hold no period params")
    starts = {min(r) for r in rows.values()}
    if len(starts) != len(devs):
        raise SmokeFailure(f"period slices start at rows {sorted(starts)} "
                           f"on {len(devs)} chips: not one slice per chip")


def reference_losses(params, b0, b1, cfg, optimizer) -> dict:
    """The plain model's losses for the launcher's first two steps: step
    0 on ``params`` and ``b0``, then one gradient and ``optimizer`` step,
    then step 1 on ``b1``; and the same losses with each planted fault."""
    from repro.models.model import loss_fn

    def loss(p, b):
        return loss_fn(p, b, cfg)[0]

    def add_sequence(acc, seq):
        # one sequence at a time: a batch's backward pass at once would
        # not fit one chip beside the params and gradients
        lg = jax.value_and_grad(loss)(params, jax.tree.map(
            lambda x: x[None], seq))
        return jax.tree.map(jnp.add, acc, lg), None

    zeros = (jnp.zeros(()), jax.tree.map(jnp.zeros_like, params))
    (l0, grads), _ = jax.lax.scan(add_sequence, zeros, b0)
    # every sequence has the same number of targets, so the batch mean is
    # the mean over sequences
    l0, grads = jax.tree.map(lambda x: x / BATCH, (l0, grads))
    p1, _ = optimizer.update(grads, optimizer.init(params), params)
    removed = {**params, "periods": jax.tree.map(
        lambda x: x.at[-1].set(0), params["periods"])}
    frozen = {**p1, "periods": jax.tree.map(
        lambda new, old: new.at[:-1].set(old[:-1]),
        p1["periods"], params["periods"])}
    return {"step 0": l0, "step 1": loss(p1, b1),
            "step 0, last layer removed": loss(removed, b0),
            "step 1, only the last layer updated": loss(frozen, b1)}


def reference_shardings(abstract, devs):
    """Each leaf split over ``devs`` along its last axis past the first
    that divides evenly (never the period-stack axis), else replicated."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(devs), ("x",))

    def one(leaf):
        for ax in range(leaf.ndim - 1, 0, -1):
            if leaf.shape[ax] % len(devs) == 0:
                return NamedSharding(mesh, P(*[None] * ax, "x"))
        return NamedSharding(mesh, P())

    return jax.tree.map(one, abstract)


def parity_phase(losses, optimizer, devs, depth: int, seq: int,
                 tiny: bool) -> None:
    """The launcher's step-0 and step-1 losses against
    ``reference_losses`` on the same params (``init_model`` from the
    launcher's seed), batches and optimizer, at the highest matmul
    precision.  XLA partitions the reference over the same chips; none of
    the repo's pipeline code runs in it."""
    from repro.configs import get_config, get_smoke_config
    from repro.data import SyntheticLM
    from repro.models.model import init_model

    cfg = (get_smoke_config if tiny else get_config)(ARCH).replace(
        n_layers=depth)
    ds = SyntheticLM(cfg.vocab_size, seq)
    b0, b1 = ({k: jnp.asarray(v) for k, v in ds.batch(i, BATCH).items()}
              for i in (0, 1))
    key = jax.random.PRNGKey(0)
    shardings = reference_shardings(
        jax.eval_shape(lambda: init_model(key, cfg)), devs)
    with jax.default_matmul_precision("highest"):
        params = jax.jit(lambda k: init_model(k, cfg),
                         out_shardings=shardings)(key)
        ref = jax.jit(lambda p, x, y: reference_losses(p, x, y, cfg,
                                                       optimizer))(
            params, b0, b1)
    ref = {k: float(v) for k, v in ref.items()}
    tol = LOSS_TOL[devs[0].platform]
    for step, got in enumerate(losses[:2]):
        want = ref[f"step {step}"]
        diff = abs(got - want)
        print(f"[parity] step {step} loss {got:.6f} reference {want:.6f} "
              f"|diff| {diff:.2e} (tolerance {tol:.0e})", flush=True)
        if not diff <= tol:
            raise SmokeFailure(f"step {step} loss {got} is {diff:.2e} from "
                               f"the reference {want} (> {tol:.0e})")
    for fault, clean in (("step 0, last layer removed", "step 0"),
                         ("step 1, only the last layer updated", "step 1")):
        witness = abs(ref[fault] - ref[clean])
        print(f"[parity] {fault}: |diff| {witness:.2e}", flush=True)
        if not witness > tol:
            raise SmokeFailure(f"{fault} moves the reference loss by only "
                               f"{witness:.2e}: the tolerance {tol:.0e} "
                               "cannot see it")


def decode_phase(depth: int, tiny: bool) -> None:
    from repro.launch import serve

    argv = ["--arch", ARCH, "--n-layers", str(depth), "--batch", "8",
            "--prompt-len", "16", "--gen", "16", "--devices", "1"]
    if tiny:
        argv.append("--smoke")
    print(f"[decode] launch.serve {' '.join(argv)}", flush=True)
    res = serve.main(argv)
    if not res["logits_finite"]:
        raise SmokeFailure("decode produced non-finite logits")
    if res["tokens"].shape != (32, 8):
        raise SmokeFailure(f"decode tokens {res['tokens'].shape}, "
                           "expected (32, 8)")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: train, parity and decode on one chip; 4: the "
                         "pipeline across four chips and its parity only")
    ap.add_argument("--tiny", action="store_true",
                    help="CPU rehearsal at smoke widths; still fails the "
                         "device check")
    args = ap.parse_args(argv)

    counters = _compile_counters()
    devs = select_devices(args.chips)
    on_tpu = devs[0].platform == "tpu"
    if not (on_tpu or args.tiny):
        raise SystemExit(f"no TPU: JAX finds {devs[0].platform} devices")
    print(f"[device] {len(devs)} x {devs[0].platform} "
          f"({devs[0].device_kind}) of {len(jax.devices())}", flush=True)

    depth = DEPTH[args.chips]
    print(f"[depth] {depth} layers: {DEPTH_WHY[args.chips]}", flush=True)
    seq = TINY_SEQ if args.tiny else SEQ
    t0 = time.perf_counter()
    res = train_phase(args.chips, depth, seq, args.tiny)
    print(f"[train] peak memory per chip: {_peak_memory(devs)}", flush=True)
    if args.chips > 1:
        check_params_on_chips(res["params"], devs)
    losses, optimizer = [loss for _, loss, _ in res["log"]], res["optimizer"]
    del res
    parity_phase(losses, optimizer, devs, depth, seq, args.tiny)
    if args.chips == 1:
        decode_phase(depth, args.tiny)
    print(f"[compile] backend compile {counters['compile_s']:.1f}s, "
          f"persistent cache hits {counters['cache_hits']} misses "
          f"{counters['cache_misses']}; wall {time.perf_counter() - t0:.1f}s",
          flush=True)

    if not on_tpu:
        raise SystemExit(f"no TPU: the phases ran on {devs[0].platform}")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
