"""Mixture-of-Experts layer: a held share of the experts, or expert
parallelism over ``ctx.ep_axis``.

Routing is the same on both paths: the router scores every routed expert
(``n_routed``, its published width) in float32 at ``HIGHEST`` precision,
takes the top-k, and optionally renormalises their weights.

* **Held experts, dropless** (no expert-parallel axis, or one of size 1).
  The layer holds ``n_experts`` contiguous experts starting at
  ``first_expert``.  Every (token, expert) pair whose expert is held is
  kept; pairs are sorted by expert and the SwiGLU experts run as grouped
  matmuls over the held experts: on a TPU the Pallas ``megablox`` kernels
  at bfloat16 operands with float32 results, as a float32 matmul at the
  TPU's default precision takes them (XLA's own ``ragged_dot`` kernel
  drops the ops' scope names), elsewhere ``lax.ragged_dot``.  Pairs routed to
  experts held elsewhere add nothing here: with one layer divided over
  several chips, each computes its own experts' part of the result.  No
  capacity, no dropped pair.
* **Expert parallel** (``ep`` > 1, the EP=DP training layout).  Tokens are
  dispatched into a per-expert capacity buffer ``(E, C, D)`` via a scatter
  (sort-free, cumsum position-in-expert), then ``all_to_all`` over the EP
  axis moves each expert's rows to the shard that owns it.  Every shard owns
  ``E / ep_size`` experts.  Pairs past an expert's capacity are dropped.

Expert FFNs are tensor-parallel over ``ctx.tp_axis`` on the ``d_ff`` dim
(row-parallel psum on the way down, same as dense MLP).  Shared experts run
on every token.  The balance loss is Switch-style (top-1 assignments against
mean router probabilities) or DeepSeek's per-sequence ``seq`` loss.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P
from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm as megablox_gmm, tgmm as megablox_tgmm

from repro import scopes

from .module import ParallelCtx, NO_PARALLEL, dense_init, split_keys, vary_all, _manual_axes
from .mlp import ACTIVATIONS, init_mlp, mlp


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int                 # experts held here (per EP shard once local)
    top_k: int
    d_ff: int                      # per-expert hidden dim (global)
    n_shared_experts: int = 0      # DeepSeek shared expert(s)
    score_fn: str = "softmax"      # "softmax" | "sigmoid"
    capacity_factor: float = 1.25  # expert-parallel path only
    aux_loss: str = "switch"       # "switch" | "seq" (DeepSeek seq_aux)
    aux_loss_weight: float = 0.01
    norm_topk_prob: bool = True    # renormalise the top-k weights to sum 1
    act: str = "silu"
    n_routed: int | None = None    # experts the router scores; None => n_experts
    first_expert: int = 0          # index of the first held expert

    @property
    def router_width(self) -> int:
        return self.n_routed or self.n_experts

    def local(self, ep: int, tp: int) -> "MoEConfig":
        assert self.n_experts % ep == 0, (self.n_experts, ep)
        assert self.d_ff % tp == 0, (self.d_ff, tp)
        return dataclasses.replace(
            self, n_experts=self.n_experts // ep, d_ff=self.d_ff // tp,
            n_routed=self.router_width)


def init_moe(key, d_model: int, cfg: MoEConfig, dtype=jnp.float32):
    """Router over every routed expert; stacked weights of the held ones."""
    ks = split_keys(key, 4)
    e_held = cfg.n_experts
    params = {
        "router": dense_init(ks[0], (d_model, cfg.router_width), in_dim=d_model,
                             dtype=jnp.float32),
        # stacked held experts (E_held, ...)
        "experts": {
            "gate": dense_init(ks[1], (e_held, d_model, cfg.d_ff), in_dim=d_model, dtype=dtype),
            "up": dense_init(ks[2], (e_held, d_model, cfg.d_ff), in_dim=d_model, dtype=dtype),
            "down": dense_init(ks[3], (e_held, cfg.d_ff, d_model), in_dim=cfg.d_ff, dtype=dtype),
        },
    }
    if cfg.n_shared_experts:
        params["shared"] = init_mlp(
            jax.random.fold_in(key, 7), d_model,
            cfg.d_ff * cfg.n_shared_experts, act=cfg.act, dtype=dtype)
    return params


def _router(params, x2d, cfg: MoEConfig, n_seq: int):
    """x2d: (T, D), ``n_seq`` sequences of T / n_seq tokens ->
    (weights (T,k), experts (T,k) int32, aux_loss scalar)."""
    e = cfg.router_width
    logits = jnp.dot(x2d.astype(jnp.float32), params["router"].astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)                 # (T, E)
    if cfg.score_fn == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = lax.top_k(scores, cfg.top_k)
    if cfg.norm_topk_prob:
        top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

    if cfg.aux_loss == "seq":
        # DeepSeek seq_aux: per sequence, f_i = E / (K S) * #choices of i,
        # P_i = mean score of i; alpha * mean over sequences of sum f_i P_i
        s = scores.reshape(n_seq, -1, e)
        counts = jax.nn.one_hot(top_e.reshape(n_seq, -1), e, dtype=jnp.float32).sum(1)
        f = counts * (e / (cfg.top_k * s.shape[1]))
        aux = jnp.mean(jnp.sum(f * s.mean(axis=1), axis=-1)) * cfg.aux_loss_weight
    else:
        # Switch-style load-balance loss: E * sum_e(frac_tokens_e * mean_prob_e)
        probs = jax.nn.softmax(logits, axis=-1)
        assign = jax.nn.one_hot(top_e[:, 0], e, dtype=jnp.float32)
        aux = e * jnp.sum(assign.mean(axis=0) * probs.mean(axis=0)) * cfg.aux_loss_weight
    return top_w, top_e, aux


def _tiling(m: int, k: int, n: int) -> tuple[int, int, int]:
    """``megablox`` tiles (rows, contraction, output): 512 rows, or the
    largest power of two under it that divides ``m`` (``_tpu_grouped``
    keeps ``m`` a multiple of 16); a width up to 1536 in one tile, a wider one in
    tiles of 512."""
    return math.gcd(m, 512), (k if k <= 1536 else 512), (n if n <= 1536 else 512)


def _unchecked(kernel, *arrays):
    """``kernel(*arrays)``, a ``megablox`` kernel whose output shapes name no
    ``vma``, in a shard_map of no further axes with the vma check off, its
    result typed varying over every manual axis as ``_grouped`` makes its
    inputs.  Only ``_gmm``'s own forward and backward rules call it, and JAX
    never transposes those, so the cast adds no reduction to the gradients."""
    if not _manual_axes():
        return kernel(*arrays)
    out = jax.shard_map(kernel, mesh=jax.sharding.get_abstract_mesh(), in_specs=P(),
                        out_specs=P(), axis_names=frozenset(), check_vma=False)(*arrays)
    return vary_all(out)


@jax.custom_vjp
def _gmm(lhs, rhs, sizes):
    """``lhs`` (m, k) rows grouped by ``sizes`` times their group's ``rhs``
    (G, k, n), in ``lhs``'s dtype.  ``sizes`` has G + 1 groups: rows of the
    last (pairs held elsewhere) and past it come out zero."""
    return _gmm_fwd(lhs, rhs, sizes)[0]


def _gmm_fwd(lhs, rhs, sizes):
    kernel = functools.partial(megablox_gmm, preferred_element_type=lhs.dtype,
                               tiling=_tiling)
    lhs, rhs = lhs.astype(jnp.bfloat16), rhs.astype(jnp.bfloat16)
    return _unchecked(kernel, lhs, rhs, sizes), (lhs, rhs, sizes)


def _gmm_bwd(res, g):
    lhs, rhs, sizes = res
    dt, g = g.dtype, g.astype(jnp.bfloat16)
    d_lhs = _unchecked(functools.partial(megablox_gmm, preferred_element_type=dt,
                                         tiling=_tiling, transpose_rhs=True),
                       g, rhs, sizes)
    d_rhs = _unchecked(functools.partial(megablox_tgmm, preferred_element_type=dt,
                                         tiling=_tiling, num_actual_groups=rhs.shape[0]),
                       lhs.swapaxes(0, 1), g, sizes)
    return d_lhs, d_rhs, None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def _tpu_grouped(lhs, rhs, sizes):
    """``_gmm`` on rows padded to a multiple of 16 (bfloat16's packing, which
    the kernels' row tiles need); the padded rows lie past every group."""
    m = lhs.shape[0]
    return _gmm(jnp.pad(lhs, ((0, -m % 16), (0, 0))), rhs, sizes)[:m]


def _grouped(lhs, rhs, sizes):
    """Grouped matmul over the held experts (``sizes``: G + 1 groups): the
    ``megablox`` kernels on a TPU, ``lax.ragged_dot`` elsewhere.  The
    platform is the one the step is compiled for, so a compile for a
    described TPU takes the kernels too."""
    lhs, rhs, sizes = vary_all((lhs, rhs, sizes))

    def elsewhere(lhs, rhs, sizes):
        return lax.ragged_dot(lhs, rhs, sizes[:-1])

    return lax.platform_dependent(lhs, rhs, sizes, tpu=_tpu_grouped, default=elsewhere)


def _held_experts(ex, x2d, top_w, top_e, cfg: MoEConfig, ctx: ParallelCtx):
    """Dropless grouped matmuls over the held experts.

    Returns (out (T, D) float32, rows computed)."""
    T, D = x2d.shape
    k, n_held = cfg.top_k, cfg.n_experts
    # pairs in (choice, token) order: pair i is token i % T's choice i // T
    local = top_e.T.reshape(-1) - cfg.first_expert          # (k*T,)
    held = (local >= 0) & (local < n_held)
    # held pairs first, grouped by expert; the others last, in a group of
    # their own that no expert computes
    group = jnp.where(held, local, n_held)
    order = jnp.argsort(group, stable=True)
    sizes = jnp.zeros((n_held + 1,), jnp.int32).at[group].add(1)
    dt = jnp.result_type(x2d, ex["gate"])                   # as ``x @ w`` promotes
    rows = x2d[order % T].astype(dt)                        # (k*T, D)
    gate, up, down = (ex[n].astype(dt) for n in ("gate", "up", "down"))
    act = ACTIVATIONS[cfg.act]
    with scopes.scope(scopes.EXPERTS):
        h = act(_grouped(rows, gate, sizes)) * _grouped(rows, up, sizes)
        y = ctx.psum_tp(_grouped(h, down, sizes))
    # back to (choice, token) order; rows in no held group come out as zeros
    y = y[jnp.argsort(order)].reshape(k, T, D).astype(jnp.float32)
    w = jnp.where(held, top_w.T.reshape(-1), 0.0).reshape(k, T, 1)
    return jnp.sum(y * w, axis=0), jnp.sum(sizes[:n_held]).astype(jnp.float32)


def _expert_parallel(ex, x2d, top_w, top_e, cfg: MoEConfig, ctx: ParallelCtx):
    """Capacity-buffer dispatch, ``all_to_all`` over ``ctx.ep_axis``.

    Returns (out (T, D) float32, rows kept)."""
    T, D = x2d.shape
    ep = ctx.ep_size
    e_local, e_global = cfg.n_experts, cfg.router_width
    assert e_local * ep == e_global, (e_local, ep, e_global)

    # --- dispatch: scatter local tokens into (E_global, C, D) capacity buffer
    cap = int(cfg.capacity_factor * T * cfg.top_k / e_global) + 1
    flat_e = top_e.reshape(-1)                      # (T*k,)
    flat_w = top_w.reshape(-1)
    flat_tok = jnp.repeat(jnp.arange(T), cfg.top_k)
    # position of each (token, expert) pair within its expert's buffer
    onehot = jax.nn.one_hot(flat_e, e_global, dtype=jnp.int32)          # (T*k, E)
    cum = jnp.cumsum(onehot, axis=0) - onehot
    pos_in_e = jnp.take_along_axis(cum, flat_e[:, None], axis=1)[:, 0]
    keep = pos_in_e < cap
    flat_w = jnp.where(keep, flat_w, 0.0)
    slot = jnp.where(keep, pos_in_e, cap - 1)

    buf = jnp.zeros((e_global, cap, D), x2d.dtype)
    buf = buf.at[flat_e, slot].add(jnp.where(keep[:, None], x2d[flat_tok], 0.0).astype(x2d.dtype))

    # --- EP exchange: rows for expert e travel to shard e // e_local
    # (E_global, C, D) -> all_to_all -> rows grouped by source shard:
    # result (E_global, C, D) where [s*e_local:(s+1)*e_local] came from shard s
    buf = ctx.all_to_all_ep(buf, split_axis=0, concat_axis=0)
    # -> (ep, e_local, C, D) -> (e_local, ep*C, D): each local expert sees
    # the rows sent by every shard.
    buf = buf.reshape(ep, e_local, cap, D).transpose(1, 0, 2, 3).reshape(e_local, ep * cap, D)

    # --- expert FFN (vmapped over local experts), TP row-parallel on down
    act = ACTIVATIONS[cfg.act]

    def expert_fn(g, u, d, rows):
        h = act(rows @ g) * (rows @ u)
        return h @ d

    with scopes.scope(scopes.EXPERTS):
        out_rows = jax.vmap(expert_fn)(ex["gate"], ex["up"], ex["down"], buf)
        out_rows = ctx.psum_tp(out_rows)

    # --- return trip
    out_rows = out_rows.reshape(e_local, ep, cap, D).transpose(1, 0, 2, 3).reshape(e_global, cap, D)
    out_rows = ctx.all_to_all_ep(out_rows, split_axis=0, concat_axis=0)

    # --- combine: gather each (token, k) slot's output, weight, and sum
    gathered = out_rows[flat_e, slot]               # (T*k, D)
    combined = jnp.zeros((T, D), jnp.float32)
    combined = combined.at[flat_tok].add(gathered.astype(jnp.float32) * flat_w[:, None])
    return combined, jnp.sum(keep).astype(jnp.float32)


def moe(params, x, cfg: MoEConfig, ctx: ParallelCtx = NO_PARALLEL):
    """x: (B, S, D) or (T, D) -> (out like x, aux_loss, expert rows).

    cfg carries local sizes (experts per EP shard, d_ff per TP shard).
    ``expert rows`` counts the (token, expert) pairs the experts here
    computed.  The ``seq`` balance loss takes each row of a (B, S, D) input
    as a sequence.
    """
    orig_shape = x.shape
    D = x.shape[-1]
    x2d = x.reshape(-1, D)
    n_seq = x.shape[0] if x.ndim == 3 else 1
    expert_parallel = ctx.ep_axis is not None and ctx.ep_size > 1

    with scopes.scope(scopes.ROUTER):
        top_w, top_e, aux = _router(params, x2d, cfg, n_seq)
        dispatch = _expert_parallel if expert_parallel else _held_experts
        out, rows = dispatch(params["experts"], x2d, top_w, top_e, cfg, ctx)
        out = out.astype(x.dtype)

    if "shared" in params:
        with scopes.scope(scopes.MLP):
            out = out + mlp(params["shared"], x2d, act=cfg.act, ctx=ctx)

    return out.reshape(orig_shape), aux, rows
