"""Compressed boundary transfers + bucketed gradient AllReduce (DESIGN.md
§10), end to end.

1. quantize -> ppermute -> dequantize round trip: the int8/fp8 wire format
   (per-tile f32 scales) crossing a real device permutation, with the
   error-feedback residual telescoping the quantization bias away,
2. the bucketed gradient stream: how gradient leaves pack into
   size-bounded buckets by their free mesh axes, and the compressed
   overlap timeline the planner prices,
3. a planner diff: the same model/cluster planned with and without the
   compression term — what the quantized wire buys on a 100 Mbps edge
   link.

    PYTHONPATH=src python examples/compressed_transfers.py
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import numpy as np  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

from repro.configs import get_smoke_config  # noqa: E402
from repro.core.costmodel import CompressionConfig  # noqa: E402
from repro.core.hardware import MBPS_100, env_b  # noqa: E402
from repro.core.planner import plan_hpp  # noqa: E402
from repro.core.profiler import LayerTable, Profile  # noqa: E402
from repro.data import SyntheticLM  # noqa: E402
from repro.kernels.quant_transfer import (  # noqa: E402
    dequantize_op, quantize_op, roundtrip, roundtrip_ef, wire_bits)
from repro.models.frontend import frontend_dim  # noqa: E402
from repro.runtime.train import (  # noqa: E402
    build_train_step, init_train_state)

B, S, M, TILE = 8, 64, 4, 256

# ---------------------------------------------------------------------------
# 1. the wire format, round-tripped through a real ppermute
# ---------------------------------------------------------------------------
print("=== 1. quantize -> ppermute -> dequantize round trip ===")
x = jax.random.normal(jax.random.PRNGKey(0), (4, 8, 128), jnp.float32)
for fmt in ("int8", "fp8"):
    packed = quantize_op(x, fmt=fmt, tile=TILE)
    x_hat = dequantize_op(packed, x.shape, x.dtype, tile=TILE)
    rel = float(jnp.max(jnp.abs(x - x_hat)) / jnp.max(jnp.abs(x)))
    bits = wire_bits(fmt, TILE)
    print(f"  {fmt}: {bits:.2f} bits/elem on the wire "
          f"({bits / 32:.3f}x of f32), round-trip rel err {rel:.4f}")

# the same payload crossing a device ring: what the pipeline's boundary
# hop does when TrainSpec.compress != "none"
devs = jax.devices()[:8]
ring = [(i, (i + 1) % 8) for i in range(8)]
packed = quantize_op(x, fmt="int8", tile=TILE)


_ring_mesh = Mesh(np.array(devs), ("r",))


@jax.jit
def _ring_hop(q, s):
    f = lambda t: jax.lax.ppermute(t, "r", ring)
    return jax.shard_map(
        lambda a, b: (f(a), f(b)), mesh=_ring_mesh,
        in_specs=jax.sharding.PartitionSpec(None),
        out_specs=jax.sharding.PartitionSpec(None), check_vma=False)(q, s)


q2, s2 = _ring_hop(packed["q"], packed["scale"])
x_hop = dequantize_op({"q": q2, "scale": s2}, x.shape, x.dtype, tile=TILE)
x_ref = dequantize_op(packed, x.shape, x.dtype, tile=TILE)
print(f"  ppermute hop preserves the payload bit-exactly: "
      f"{bool(jnp.array_equal(x_hop, x_ref))}")

# error feedback: the residual carries what quantization dropped, so the
# *sum* of T compressed rounds converges on the sum of the raw tensors
err = jnp.zeros_like(x)
tot = jnp.zeros_like(x)
T = 8
for _ in range(T):
    x_hat, err = roundtrip_ef(x, err, fmt="int8", tile=TILE)
    tot = tot + x_hat
one_shot = float(jnp.max(jnp.abs(roundtrip(x, fmt="int8", tile=TILE) - x)))
bias = float(jnp.max(jnp.abs(tot / T - x)))
print(f"  error feedback over {T} rounds: per-round bias {bias:.2e} vs "
      f"one-shot {one_shot:.2e} ({one_shot / max(bias, 1e-12):.0f}x smaller)")

# ---------------------------------------------------------------------------
# 2. the bucketed gradient stream on the real runtime
# ---------------------------------------------------------------------------
print("\n=== 2. bucketed + compressed gradient AllReduce ===")
cfg = get_smoke_config("phi3-mini-3.8b")
mesh = Mesh(np.array(devs).reshape(2, 4), ("data", "model"))
ts = build_train_step(cfg, mesh, global_batch=B, stage=2, n_micro=M,
                      compress="int8", bucket_mb=4.0)
print(f"  spec: compress={ts.spec.compress} bucket_mb={ts.spec.bucket_mb} "
      f"error_feedback={ts.spec.error_feedback}")
print(f"  {len(ts.buckets)} buckets (leaves grouped by the mesh axes their "
      f"psum reduces over, packed to the size cap):")
for bi, (free, idxs, sizes) in enumerate(ts.buckets):
    mb = sum(sizes) * 4 / 2**20
    print(f"    bucket {bi}: reduce over {free or '(none)'} — "
          f"{len(idxs)} leaves, {sum(sizes):,} elems "
          f"({mb:.2f} MiB raw, {mb * (8 + 32 / TILE) / 32:.2f} MiB wired)")

key = jax.random.PRNGKey(0)
params, opt_state = init_train_state(key, ts)
ds = SyntheticLM(cfg.vocab_size, S, n_codebooks=cfg.n_codebooks,
                 prefix_len=cfg.prefix_len, prefix_dim=frontend_dim(cfg))
batch = ts.shard_batch(ds.batch(0, B))
ef = ts.init_ef()
(loss0, _), grads, ef = ts.grad_fn(params, batch, ef)
ef_mag = max(float(jnp.max(jnp.abs(v))) for v in jax.tree.leaves(ef))
print(f"  one compressed grad round: loss {float(loss0):.4f}, "
      f"largest carried residual {ef_mag:.2e}")
params, opt_state, ef, l0, _ = ts.step_fn(params, opt_state, ef, batch)
l1, _ = ts.loss_fn(params, batch)
print(f"  compressed step: loss {float(l0):.4f} -> {float(l1):.4f}")

# ---------------------------------------------------------------------------
# 3. the planner diff: what the quantized wire buys at 100 Mbps
# ---------------------------------------------------------------------------
print("\n=== 3. plan with vs without the compression term ===")
table = LayerTable.from_model_config(cfg, S)
cluster = env_b(MBPS_100).sorted_by_memory()
prof = Profile.analytic(table, cluster, max_batch=B)
raw = plan_hpp(prof, B, micro_batch=2, arch=cfg.name, staleness=1)
comp = plan_hpp(prof, B, micro_batch=2, arch=cfg.name, staleness=1,
                compress=CompressionConfig(fmt="int8", tile=TILE,
                                           bucket_mb=4.0))
auto = plan_hpp(prof, B, micro_batch=2, arch=cfg.name, staleness=1,
                compress="auto")
print(f"  raw wire:        {raw.latency * 1e3:8.1f} ms/round")
print(f"  int8 wire:       {comp.latency * 1e3:8.1f} ms/round "
      f"({raw.latency / comp.latency:.2f}x)")
print(f"  compress='auto': {auto.latency * 1e3:8.1f} ms/round — planner "
      f"chose {auto.compress.fmt if auto.compress else 'no compression'}")
for tag, plan in (("raw", raw), ("int8", comp)):
    comm = [s for s in plan.steps if s.kind == "comm"]
    if comm:
        print(f"    {tag}: boundary transfer {comm[0].ef * 1e3:.2f} ms fwd / "
              f"{comm[0].eb * 1e3:.2f} ms bwd per micro-batch")
assert comp.latency <= raw.latency * (1 + 1e-9)
print("\nOK: compressed plan is never priced slower than the raw plan")
