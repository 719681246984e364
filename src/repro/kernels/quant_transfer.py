"""Pallas quantize/dequantize kernels for compressed boundary transfers.

The wire format (DESIGN.md §10): a float tensor is flattened, zero-padded
to a multiple of ``tile`` elements and viewed as ``(R, tile)`` — one
*scale tile* per row.  ``quantize_tiles`` emits the packed payload
``q (R, tile)`` in int8 (symmetric round-to-nearest, clipped to ±127) or
fp8 (e4m3) plus per-tile fp32 scales ``(R, 1)``; ``dequantize_tiles``
reconstructs ``q * scale``.  Both payloads travel through the runtime's
``ppermute`` / ``psum`` collectives, so compressed int8 moves
``(1 + 4/tile) / 4`` of the fp32 bytes (``costmodel.CompressionConfig``
prices exactly this ratio).

Kernels grid over row blocks; each step reduces its block's row-wise
abs-max in registers and writes payload + scales in one pass.  On CPU
(no TPU backend) the dispatch wrappers fall back to the pure-jnp oracles
in ``kernels.ref`` — the SAME arithmetic ops in the same order, so
kernel-vs-reference parity is bitwise (``tests/test_kernels.py``) and the
distributed runtime's numerics do not depend on the backend.

``roundtrip_ef`` is the error-feedback form used for the gradient stream:
the residual ``e_t`` of round t is added to round t+1's tensor before
quantization, so the *running sum* of transmitted gradients telescopes to
the true sum up to one residual (bias → 0 as 1/T over steps).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .ref import naive_dequantize_tiles, naive_quantize_tiles, quant_scale

QUANT_FORMATS = ("int8", "fp8")
#: power-of-two scale divisor per format (exact fp division — see
#: ``ref.quant_scale``); int8 payloads clip to the symmetric [-127, 127]
QDIV = {"int8": 128.0, "fp8": 256.0}


def quant_dtype(fmt: str):
    if fmt == "int8":
        return jnp.int8
    if fmt == "fp8":
        return jnp.float8_e4m3fn
    raise ValueError(f"unknown quantization format {fmt!r} "
                     f"(expected one of {QUANT_FORMATS})")


def wire_bits(fmt: str, tile: int) -> float:
    """Payload bits per element including the amortized per-tile scale."""
    return 8.0 + 32.0 / tile


# ---------------------------------------------------------------------------
# Pallas kernels
# ---------------------------------------------------------------------------


def _row_blocks(R: int, want: int) -> tuple[int, int]:
    """``(block_rows, grid)`` over R rows: blocks of ``want`` rows, or one
    block of all R rows when there are fewer.  ``want`` is a multiple of 8,
    the TPU's sublane tile, so every block shape is one the chip accepts.
    The last block may be ragged: rows are independent, and the rows of a
    block that fall past R are never written back."""
    if want % 8:
        raise ValueError(f"block_rows {want} is not a multiple of 8")
    b = min(want, R)
    return b, pl.cdiv(R, b)


def _quantize_kernel(x_ref, q_ref, s_ref, *, fmt: str):
    x = x_ref[...].astype(jnp.float32)
    amax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    scale = quant_scale(amax, fmt)
    y = x / scale
    if fmt == "int8":
        y = jnp.clip(jnp.round(y), -127.0, 127.0)
    q_ref[...] = y.astype(q_ref.dtype)
    s_ref[...] = scale


def _dequantize_kernel(q_ref, s_ref, o_ref):
    o_ref[...] = (q_ref[...].astype(jnp.float32) * s_ref[...]).astype(
        o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("fmt", "block_rows", "interpret"))
def quantize_tiles(x, *, fmt: str = "int8", block_rows: int = 512,
                   interpret: bool = False):
    """x: (R, tile) float -> (q (R, tile) int8/fp8, scales (R, 1) f32).

    The kernel is named ``quantize_<fmt>``, so a chip compiler that
    refuses a format names it in the error and in profiler traces."""
    R, T = x.shape
    block_rows, grid = _row_blocks(R, block_rows)
    return pl.pallas_call(
        functools.partial(_quantize_kernel, fmt=fmt),
        grid=(grid,),
        in_specs=[pl.BlockSpec((block_rows, T), lambda i: (i, 0))],
        out_specs=(pl.BlockSpec((block_rows, T), lambda i: (i, 0)),
                   pl.BlockSpec((block_rows, 1), lambda i: (i, 0))),
        out_shape=(jax.ShapeDtypeStruct((R, T), quant_dtype(fmt)),
                   jax.ShapeDtypeStruct((R, 1), jnp.float32)),
        interpret=interpret,
        name=f"quantize_{fmt}",
    )(x)


@functools.partial(jax.jit, static_argnames=("out_dtype", "block_rows",
                                             "interpret"))
def dequantize_tiles(q, scales, *, out_dtype=jnp.float32,
                     block_rows: int = 512, interpret: bool = False):
    """(q (R, tile), scales (R, 1)) -> (R, tile) ``out_dtype``."""
    R, T = q.shape
    block_rows, grid = _row_blocks(R, block_rows)
    return pl.pallas_call(
        _dequantize_kernel,
        grid=(grid,),
        in_specs=[pl.BlockSpec((block_rows, T), lambda i: (i, 0)),
                  pl.BlockSpec((block_rows, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_rows, T), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, T), out_dtype),
        interpret=interpret,
        name=f"dequantize_{jnp.dtype(q.dtype).name}",
    )(q, scales)


# ---------------------------------------------------------------------------
# Dispatch + packing (the runtime entry points)
# ---------------------------------------------------------------------------


def _use_kernel() -> bool:
    return jax.default_backend() == "tpu"


def pack_tiles(x, tile: int):
    """Flatten and zero-pad ``x`` to the (R, tile) wire layout."""
    flat = x.reshape(-1).astype(jnp.float32)
    n = flat.shape[0]
    R = -(-n // tile)
    pad = R * tile - n
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), jnp.float32)])
    return flat.reshape(R, tile)


def unpack_tiles(x2d, shape, dtype):
    """Inverse of ``pack_tiles``: strip padding, restore shape/dtype."""
    n = 1
    for d in shape:
        n *= d
    return x2d.reshape(-1)[:n].reshape(shape).astype(dtype)


def quantize_op(x, *, fmt: str = "int8", tile: int = 256):
    """Quantize an arbitrary-shape tensor into the wire pytree
    ``{"q": (R, tile) int8/fp8, "scale": (R, 1) f32}`` — the payload the
    pipeline's ``ppermute`` (and any other collective) actually moves."""
    x2d = pack_tiles(x, tile)
    if _use_kernel():
        q, s = quantize_tiles(x2d, fmt=fmt)
    else:
        q, s = naive_quantize_tiles(x2d, fmt=fmt)
    return {"q": q, "scale": s}


def dequantize_op(packed, shape, dtype, *, tile: int = 256):
    """Reconstruct the tensor from the wire pytree on the receiver."""
    if _use_kernel():
        x2d = dequantize_tiles(packed["q"], packed["scale"])
    else:
        x2d = naive_dequantize_tiles(packed["q"], packed["scale"])
    return unpack_tiles(x2d, shape, dtype)


def roundtrip(x, *, fmt: str = "int8", tile: int = 256):
    """quantize -> dequantize (what the receiver sees of ``x``)."""
    return dequantize_op(quantize_op(x, fmt=fmt, tile=tile), x.shape, x.dtype,
                         tile=tile)


def roundtrip_ef(x, err, *, fmt: str = "int8", tile: int = 256):
    """Error-feedback round trip: returns ``(x_hat, new_err)``.

    The accumulated residual ``err`` (same shape as ``x``) is folded into
    the tensor before quantization and the fresh quantization error becomes
    the next residual: ``sum_t x_hat_t = sum_t x_t + e_0 - e_T``, so the
    transmitted stream is unbiased up to one trailing residual.
    """
    comp = x.astype(jnp.float32) + err.astype(jnp.float32)
    x_hat = roundtrip(comp, fmt=fmt, tile=tile)
    return x_hat.astype(x.dtype), (comp - x_hat).astype(err.dtype)
