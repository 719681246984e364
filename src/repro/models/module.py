"""Minimal pure-JAX module utilities (no flax).

Parameters are nested dicts of jnp arrays ("param trees").  Every layer is a
pair of pure functions::

    init_<layer>(key, cfg, ...) -> params
    <layer>(params, x, *, ctx, ...) -> y

``ParallelCtx`` carries the SPMD context (mesh axis names) so the same layer
code runs single-device (all axes ``None``) and inside ``shard_map`` with
Megatron-style tensor parallelism / expert parallelism.  All collectives are
routed through the ctx so they are no-ops outside shard_map.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax

PyTree = Any


# ---------------------------------------------------------------------------
# Parallel context
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ParallelCtx:
    """SPMD context threaded through every layer.

    tp_axis:  tensor-parallel mesh axis (Megatron-style).  Weight matrices are
              sharded on heads / ffn / vocab dims; each device sees *local*
              shapes.  ``psum_tp`` reduces row-parallel matmul partials.
    ep_axis:  expert-parallel axis for MoE all_to_all dispatch.
    dp_axes:  data-parallel axes (gradient reduction happens outside layers).
    seq_axis: axis over which a decode KV cache is sequence-sharded
              (flash-decoding style partial-softmax combine).
    """

    tp_axis: str | None = None
    tp_size: int = 1
    ep_axis: str | None = None
    ep_size: int = 1
    dp_axes: tuple[str, ...] = ()
    seq_axis: str | None = None
    seq_size: int = 1

    # -- collective helpers -------------------------------------------------
    def psum_tp(self, x):
        return lax.psum(x, self.tp_axis) if self.tp_axis is not None else x

    def pmax_tp(self, x):
        return lax.pmax(x, self.tp_axis) if self.tp_axis is not None else x

    def psum_seq(self, x):
        return lax.psum(x, self.seq_axis) if self.seq_axis is not None else x

    def pmax_seq(self, x):
        return lax.pmax(x, self.seq_axis) if self.seq_axis is not None else x

    def tp_index(self):
        if self.tp_axis is None:
            return jnp.int32(0)
        return lax.axis_index(self.tp_axis)

    def ep_index(self):
        if self.ep_axis is None:
            return jnp.int32(0)
        return lax.axis_index(self.ep_axis)

    def seq_index(self):
        if self.seq_axis is None:
            return jnp.int32(0)
        return lax.axis_index(self.seq_axis)

    def all_to_all_ep(self, x, split_axis: int, concat_axis: int):
        if self.ep_axis is None:
            return x
        return lax.all_to_all(
            x, self.ep_axis, split_axis=split_axis, concat_axis=concat_axis, tiled=True
        )


NO_PARALLEL = ParallelCtx()


# ---------------------------------------------------------------------------
# vma-robust scan (works the same inside and outside shard_map)
# ---------------------------------------------------------------------------


def _manual_axes() -> tuple:
    """Manual mesh axes of the enclosing shard_map (empty outside one)."""
    return tuple(jax.sharding.get_abstract_mesh().manual_axes)


def pcast_varying(x, axes):
    """Idempotently mark ``x`` varying over ``axes``.  pcast is a pure
    type operation — no communication."""
    need = tuple(a for a in axes if a not in jax.typeof(x).vma)
    return lax.pcast(x, need, to="varying") if need else x


def vary_all(tree: PyTree) -> PyTree:
    """Mark every leaf varying over all manual mesh axes (no-op outside
    shard_map)."""
    axes = _manual_axes()
    if not axes:
        return tree
    return jax.tree.map(lambda x: pcast_varying(x, axes), tree)


def vscan(body: Callable, init, xs, **kw):
    """``lax.scan`` whose carry typing is robust under shard_map: the initial
    carry and each step's output carry are cast varying over all manual axes,
    so layer code does not need to reason about vma propagation."""
    axes = _manual_axes()
    if not axes:
        return lax.scan(body, init, xs, **kw)

    def wrapped(carry, x):
        carry, y = body(carry, x)
        return vary_all(carry), y

    return lax.scan(wrapped, vary_all(init), xs, **kw)


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------


def dense_init(key, shape, in_dim: int | None = None, dtype=jnp.float32, scale: float = 1.0):
    """Truncated-normal fan-in init (matches common LM init)."""
    if in_dim is None:
        in_dim = shape[-2] if len(shape) >= 2 else shape[-1]
    std = scale / math.sqrt(in_dim)
    return (jax.random.truncated_normal(key, -3.0, 3.0, shape) * std).astype(dtype)


def embed_init(key, shape, dtype=jnp.float32):
    return (jax.random.normal(key, shape) * 0.02).astype(dtype)


def zeros_init(_key, shape, dtype=jnp.float32):
    return jnp.zeros(shape, dtype)


def ones_init(_key, shape, dtype=jnp.float32):
    return jnp.ones(shape, dtype)


def split_keys(key, n: int):
    return list(jax.random.split(key, n))


# ---------------------------------------------------------------------------
# Param tree utilities
# ---------------------------------------------------------------------------


def tree_size(tree: PyTree) -> int:
    """Total number of parameters."""
    return sum(x.size for x in jax.tree.leaves(tree))


def tree_bytes(tree: PyTree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def tree_cast(tree: PyTree, dtype) -> PyTree:
    return jax.tree.map(lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x, tree)


def tree_zeros_like(tree: PyTree) -> PyTree:
    return jax.tree.map(jnp.zeros_like, tree)


def assert_finite(tree: PyTree, name: str = "tree") -> None:
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        if jnp.issubdtype(leaf.dtype, jnp.floating):
            if not bool(jnp.isfinite(leaf).all()):
                raise AssertionError(f"non-finite values in {name}{jax.tree_util.keystr(path)}")


def stack_trees(trees: list[PyTree]) -> PyTree:
    """Stack a list of identically-structured param trees along a new axis 0."""
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *trees)


def unstack_tree(tree: PyTree, n: int) -> list[PyTree]:
    return [jax.tree.map(lambda x, i=i: x[i], tree) for i in range(n)]


def vmap_init(init_fn: Callable, key, n: int, *args, **kwargs) -> PyTree:
    """Initialize ``n`` stacked copies of a layer (for scan-over-layers)."""
    keys = jax.random.split(key, n)
    return jax.vmap(lambda k: init_fn(k, *args, **kwargs))(keys)
