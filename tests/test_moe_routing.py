"""MoE routing invariants (single-device semantics, the expert-parallel
path under a vmapped ``data`` axis, properties)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis",
                    reason="property tests need the 'test' extra")
import hypothesis.strategies as st
from hypothesis import given, settings

from repro.models.module import ParallelCtx
from repro.models.moe import MoEConfig, init_moe, moe
from repro.models.mlp import ACTIVATIONS


def setup_moe(key, d=64, e=8, k=2, cap=8.0, score="softmax", shared=0):
    cfg = MoEConfig(n_experts=e, top_k=k, d_ff=128, capacity_factor=cap,
                    score_fn=score, n_shared_experts=shared)
    params = init_moe(key, d, cfg)
    return cfg, params


def test_moe_output_shape_and_finite():
    key = jax.random.PRNGKey(0)
    cfg, params = setup_moe(key)
    x = jax.random.normal(key, (4, 16, 64)) * 0.5
    out, aux, _ = moe(params, x, cfg)
    assert out.shape == x.shape
    assert bool(jnp.isfinite(out).all()) and bool(jnp.isfinite(aux))


def test_moe_no_drop_equals_dense_mixture():
    """The held-expert path drops nothing: the MoE output equals the
    explicit top-k weighted mixture of expert MLPs."""
    key = jax.random.PRNGKey(1)
    cfg, params = setup_moe(key, cap=64.0)
    x = jax.random.normal(key, (2, 8, 64)) * 0.5
    out, _, rows = moe(params, x, cfg)
    assert float(rows) == 2 * 8 * cfg.top_k

    x2 = x.reshape(-1, 64)
    logits = x2 @ params["router"]
    scores = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(scores, cfg.top_k)
    top_w = top_w / top_w.sum(-1, keepdims=True)
    ex = params["experts"]
    act = ACTIVATIONS[cfg.act]

    def expert(e_idx, rows):
        h = act(rows @ ex["gate"][e_idx]) * (rows @ ex["up"][e_idx])
        return h @ ex["down"][e_idx]

    ref = jnp.zeros_like(x2)
    for i in range(x2.shape[0]):
        acc = sum(top_w[i, j] * expert(top_e[i, j], x2[i][None])[0]
                  for j in range(cfg.top_k))
        ref = ref.at[i].set(acc)
    np.testing.assert_allclose(np.asarray(out.reshape(-1, 64)), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)


def expert_parallel(params, x, cfg, ep=2):
    """``moe`` on ``ep`` expert-parallel shards (a vmapped ``data`` axis):
    each shard holds ``E / ep`` experts and routes its own tokens."""
    local = cfg.local(ep, 1)
    ctx = ParallelCtx(ep_axis="data", ep_size=ep)
    shard = jax.tree.map(lambda w: w.reshape(ep, -1, *w.shape[1:]),
                         params["experts"])
    xs = x.reshape(ep, -1, *x.shape[1:])

    def one(experts, x):
        return moe({**params, "experts": experts}, x, local, ctx)

    out, aux, rows = jax.vmap(one, axis_name="data")(shard, xs)
    return out.reshape(x.shape), aux, rows.sum()


def test_moe_capacity_drops_tokens():
    """On the expert-parallel path, tiny capacity must reduce the output
    norm (dropped tokens emit 0); ample capacity drops nothing and matches
    the held-expert path."""
    key = jax.random.PRNGKey(2)
    cfg_full, params = setup_moe(key, cap=64.0)
    cfg_tight = dataclasses.replace(cfg_full, capacity_factor=0.25)
    x = jax.random.normal(key, (2, 32, 64)) * 0.5
    out_full, _, rows_full = expert_parallel(params, x, cfg_full)
    out_tight, _, rows_tight = expert_parallel(params, x, cfg_tight)
    assert float(jnp.linalg.norm(out_tight)) < float(jnp.linalg.norm(out_full))
    assert float(rows_tight) < float(rows_full) == 2 * 32 * cfg_full.top_k
    # each shard routes one sequence of 32 tokens on its own
    held = jnp.concatenate([moe(params, x[i:i + 1], cfg_full)[0]
                            for i in range(2)])
    np.testing.assert_allclose(np.asarray(out_full), np.asarray(held),
                               atol=1e-5, rtol=1e-5)


def test_moe_sigmoid_scores_and_shared_expert():
    key = jax.random.PRNGKey(3)
    cfg, params = setup_moe(key, score="sigmoid", shared=1)
    assert "shared" in params
    x = jax.random.normal(key, (2, 8, 64)) * 0.5
    out, aux, _ = moe(params, x, cfg)
    assert bool(jnp.isfinite(out).all())


@given(e=st.sampled_from([4, 8]), k=st.integers(1, 3),
       t=st.sampled_from([8, 32]))
@settings(max_examples=8, deadline=None)
def test_moe_grad_finite_property(e, k, t):
    key = jax.random.PRNGKey(e * 10 + k)
    cfg, params = setup_moe(key, e=e, k=min(k, e))
    x = jax.random.normal(key, (1, t, 64)) * 0.5

    def loss(p):
        out, aux, _ = moe(p, x, cfg)
        return jnp.sum(out ** 2) + aux

    grads = jax.grad(loss)(params)
    for g in jax.tree.leaves(grads):
        assert bool(jnp.isfinite(g).all())


@pytest.mark.parametrize("rows", [96, 90])
def test_tpu_grouped_matmul_matches_ragged_dot(monkeypatch, rows):
    """The TPU's grouped matmul (the ``megablox`` kernels, run here in
    interpret mode) against ``lax.ragged_dot``, forward and both gradients.
    The last group holds pairs routed elsewhere: its rows come out zero and
    take no gradient.  90 rows do not tile by 16, so the kernels get them
    padded to 96.  Inputs and the cotangent are bfloat16 values, which
    the kernels take exactly, so only the order of the float32 sums
    differs.  (Interpret mode runs the kernels as loops that a shard_map's
    vma typing rejects, so ``_unchecked``'s shard_map is left to the
    compile for a TPU in ``test_tpu_compile.py``.)"""
    from functools import partial

    from jax import lax

    from repro.models import moe as moe_mod

    monkeypatch.setattr(moe_mod, "megablox_gmm",
                        partial(moe_mod.megablox_gmm, interpret=True))
    monkeypatch.setattr(moe_mod, "megablox_tgmm",
                        partial(moe_mod.megablox_tgmm, interpret=True))
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    bf16 = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)
    lhs = bf16(jax.random.normal(ks[0], (rows, 256)))
    rhs = bf16(jax.random.normal(ks[1], (3, 256, 128)))
    ct = bf16(jax.random.normal(ks[2], (rows, 128)))
    sizes = jnp.array([20, 0, 37, rows - 57], jnp.int32)

    def loss(matmul, l, r):
        y = matmul(l, r)
        return jnp.sum(y * ct), y

    def want(l, r):
        return lax.ragged_dot(l, r, sizes[:-1], precision=lax.Precision.HIGHEST)

    def got(l, r):
        return moe_mod._tpu_grouped(l, r, sizes)

    grad = partial(jax.value_and_grad, argnums=(0, 1), has_aux=True)
    (_, y_want), g_want = grad(partial(loss, want))(lhs, rhs)
    (_, y_got), g_got = jax.jit(grad(partial(loss, got)))(lhs, rhs)
    np.testing.assert_allclose(np.asarray(y_got), np.asarray(y_want),
                               rtol=1e-5, atol=1e-4)
    assert not np.any(np.asarray(y_got[57:]))
    for a, b in zip(g_got, g_want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-4)
    assert not np.any(np.asarray(g_got[0][57:]))
