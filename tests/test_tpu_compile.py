"""Compiles for a described TPU v5e chip; no chip is needed.

The chip's compiler refuses what interpret mode and the CPU accept: block
shapes off the sublane tiling, a program larger than the chip's memory.
These tests compile, at phi3-mini-3.8b's published widths, the quantized
boundary-transfer kernels the runtime dispatches on TPU and one decoder
layer's forward and backward pass, and, at DeepSeek-V2-Lite's, one expert
layer's, whose held experts run as grouped-matmul kernels.

The topology is described inside a fixture and never while a module is
imported: one process at a time may load the TPU library, and every test
worker imports this file.
"""

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.quant_transfer import (dequantize_tiles, quant_dtype,
                                          quantize_tiles)
from repro.models.blocks import apply_period, init_period

ARCH = "phi3-mini-3.8b"
MICRO_BATCH, SEQ, TILE = 2, 2048, 256
V5E_HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def topo():
    mp = pytest.MonkeyPatch()
    mp.setenv("TPU_LOG_DIR", "disabled")     # else the compiler logs to /tmp
    from jax.experimental import topologies
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        mp.undo()
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    mp.undo()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A program compiled for a described chip is written to the persistent
    cache but cannot be read back without one; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _on(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# (R, 256) rows of one micro-batch's boundary activation (2 x 2048 x 3072),
# and a row count that is not a multiple of the 8-row sublane tile
@pytest.mark.parametrize("rows", [MICRO_BATCH * SEQ * 3072 // TILE, 19])
@pytest.mark.parametrize("fmt", ["int8", "fp8"])
def test_quant_kernels_compile_for_v5e(one_chip, no_persistent_cache, fmt,
                                       rows):
    """Both formats compile on v5e (fp8 e4m3 included), as Pallas kernels
    rather than a fallback."""
    q = quantize_tiles.lower(_on(one_chip, (rows, TILE), jnp.float32),
                             fmt=fmt).compile()
    assert "tpu_custom_call" in q.as_text()
    d = dequantize_tiles.lower(
        _on(one_chip, (rows, TILE), quant_dtype(fmt)),
        _on(one_chip, (rows, 1), jnp.float32)).compile()
    assert "tpu_custom_call" in d.as_text()


def test_phi3_layer_fwd_bwd_fits_one_v5e(one_chip, no_persistent_cache):
    cfg = get_config(ARCH)
    abstract = jax.eval_shape(lambda k: init_period(k, cfg),
                              jax.random.PRNGKey(0))
    params = jax.tree.map(lambda a: _on(one_chip, a.shape, a.dtype), abstract)
    x = _on(one_chip, (MICRO_BATCH, SEQ, cfg.d_model), cfg.cdtype)
    pos = _on(one_chip, (MICRO_BATCH, SEQ), jnp.int32)

    def fwd_bwd(p, x, pos):
        def loss(p, x):
            y, stats = apply_period(p, x, pos, cfg)
            return jnp.sum(y.astype(jnp.float32)) + stats["aux"]
        return jax.grad(loss, argnums=(0, 1))(p, x)

    compiled = jax.jit(fwd_bwd).lower(params, x, pos).compile()
    ma = compiled.memory_analysis()
    used = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert 0 < used < V5E_HBM_BYTES, ma


def test_expert_layer_compiles_to_scoped_grouped_matmuls(topo, no_persistent_cache):
    """One DeepSeek-V2-Lite expert layer (8 of 64 experts held), forward
    and backward inside a shard_map as the stage runs it: the held experts'
    matmuls are the ``megablox`` kernels (three forward, six backward), each
    under the ``experts`` scope, so a trace puts their time there."""
    import dataclasses
    import re

    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from repro import scopes

    cfg = get_config("deepseek-v2-lite-16b")
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, n_experts=8))
    mesh = Mesh(np.array(topo.devices[:1]), ("model",))
    rep = jax.sharding.NamedSharding(mesh, P())
    abstract = jax.eval_shape(lambda k: init_period(k, cfg), jax.random.PRNGKey(0))
    params = jax.tree.map(lambda a: _on(rep, a.shape, a.dtype), abstract)
    x = _on(rep, (1, 512, cfg.d_model), cfg.cdtype)
    pos = _on(rep, (1, 512), jnp.int32)

    @jax.jit
    @jax.shard_map(mesh=mesh, in_specs=(P(), P(), P()), out_specs=P())
    def fwd_bwd(p, x, pos):
        def loss(p, x):
            y, stats = apply_period(p, x, pos, cfg)
            return jnp.sum(y.astype(jnp.float32)) + stats["aux"]
        return jax.lax.psum(jax.grad(loss, argnums=(0, 1))(p, x), "model")

    text = fwd_bwd.lower(params, x, pos).compile().as_text()
    kernels = [re.search(r'op_name="([^"]*)"', line).group(1)
               for line in text.splitlines()
               if re.search(r"custom_call_target=\"tpu_custom_call\"", line)
               and "gmm" in line]
    assert len(kernels) == 9, kernels
    assert all(f"{scopes.PREFIX}/{scopes.EXPERTS}/" in k for k in kernels), kernels
