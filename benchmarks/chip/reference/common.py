"""What every plain reference shares: seeded weights, AdamW, a gradient
blocked over sequences, and per-leaf norms.

Weights are drawn leaf by leaf from ``fold_in(key, i)`` in the order a
family's ``init`` asks for them, so they are a pure function of the seed.
The tree uses the program's leaf names (``periods.layers[0].attn.wq`` and
so on) with every per-layer leaf stacked on a leading layer axis; the
benchmark hands the same tree to the program, laid out as the program
lays out its own.
"""

from __future__ import annotations

import importlib
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def seed_key(seed: int):
    """A PRNG key for any non-negative seed (64-bit seeds do not collide)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def family(name: str):
    """The reference module of a model family (``reference/<name>.py``)."""
    return importlib.import_module(f"benchmarks.chip.reference.{name}")


class Draw:
    """Leaf initializers, each drawing from the next key of ``key``."""

    def __init__(self, key):
        self.key, self.n = key, 0

    def _next(self):
        k = jax.random.fold_in(self.key, self.n)
        self.n += 1
        return k

    def normal(self, shape, std: float):
        return jax.random.normal(self._next(), shape, jnp.float32) * std

    def fan_in(self, shape):
        """Truncated normal (at 3 sigma) scaled by 1/sqrt(fan-in), the
        fan-in being the second-to-last axis."""
        std = 1.0 / math.sqrt(shape[-2])
        return jax.random.truncated_normal(self._next(), -3.0, 3.0, shape,
                                           jnp.float32) * std

    def uniform(self, shape, lo: float, hi: float):
        return jax.random.uniform(self._next(), shape, jnp.float32, lo, hi)

    @staticmethod
    def ones(shape):
        return jnp.ones(shape, jnp.float32)


def init_params(fam, seed: int, model: dict):
    return fam.init(Draw(seed_key(seed)), model)


def operand(x, precision: str):
    """A matmul operand in ``precision``: "float32" (the reference),
    "bfloat16" (rounded to it, and its gradient too, as a matmul at the
    TPU's default precision rounds both), or "int8" (the control: rounded
    to int8 with one symmetric scale per tensor in the forward pass, the
    gradient passed straight through)."""
    if precision == "int8":
        scale = lax.stop_gradient(jnp.max(jnp.abs(x)) / 127.0 + 1e-30)
        return x + lax.stop_gradient(jnp.round(x / scale) * scale - x)
    if precision == "bfloat16":
        return x.astype(jnp.bfloat16).astype(x.dtype)
    if precision != "float32":
        raise ValueError(f"no matmul precision {precision!r}")
    return x


def dot(x, w, precision: str):
    """``x @ w`` with both operands in ``precision``."""
    return jnp.matmul(operand(x, precision), operand(w, precision))


def rmsnorm(scale, x, eps: float):
    xf = x.astype(jnp.float32)
    xf = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * scale).astype(x.dtype)


def next_token_ce(h, head, tokens, precision: str):
    """Mean cross entropy of ``h[:, t] @ head`` predicting ``tokens[:, t+1]``
    (the logits in float32)."""
    logits = dot(h[:, :-1], head, precision).astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)


# ---------------------------------------------------------------------------
# Training
# ---------------------------------------------------------------------------


def learning_rate(opt: dict, step):
    """Linear warm-up then cosine decay to ``floor`` of the peak, at the
    1-based update ``step`` (a traced scalar)."""
    s = jnp.asarray(step, jnp.float32)
    warm, total = opt["warmup"], opt["total"]
    prog = jnp.clip((s - warm) / max(total - warm, 1), 0.0, 1.0)
    cos = opt["floor"] + (1 - opt["floor"]) * 0.5 * (1 + jnp.cos(jnp.pi * prog))
    return opt["lr"] * jnp.where(s < warm, s / max(warm, 1), cos)


def adamw(params, m, v, grads, step, opt: dict):
    """One AdamW update at the 1-based ``step``, global-norm clipping
    first.  Returns ``(params, m, v, clipped grads)``."""
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, opt["grad_clip"] / (gnorm + 1e-9))
    grads = jax.tree.map(lambda g: g * scale, grads)
    b1, b2, eps, wd = opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"]
    m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda v_, g: b2 * v_ + (1 - b2) * g * g, v, grads)
    s = jnp.asarray(step, jnp.float32)
    bc1, bc2 = 1 - b1 ** s, 1 - b2 ** s
    lr = learning_rate(opt, s)

    def upd(p, m_, v_):
        u = (m_ / bc1) / (jnp.sqrt(v_ / bc2) + eps)
        return p - lr * (u + wd * p)

    return jax.tree.map(upd, params, m, v), m, v, grads


def blocked_loss_and_grad(loss_fn, params, tokens):
    """Mean loss and gradient over the rows of ``tokens``, one sequence at
    a time (every sequence has the same number of targets, so the mean of
    the sequences' means is the batch mean)."""

    def add(acc, row):
        lg = jax.value_and_grad(loss_fn)(params, row[None])
        return jax.tree.map(jnp.add, acc, lg), None

    n = tokens.shape[0]
    zeros = (jnp.zeros((), jnp.float32), jax.tree.map(jnp.zeros_like, params))
    (loss, grads), _ = lax.scan(add, zeros, tokens)
    return loss / n, jax.tree.map(lambda g: g / n, grads)


# ---------------------------------------------------------------------------
# Norms and gaps
# ---------------------------------------------------------------------------


def leaf_norms(tree):
    """Norm of every leaf; leaves under ``periods`` get one norm per row of
    their leading (layer) axis."""

    def one(path, x):
        x = x.astype(jnp.float32)
        if jax.tree_util.keystr(path).startswith("['periods']"):
            return jnp.sqrt(jnp.sum(x * x, axis=tuple(range(1, x.ndim))))
        return jnp.sqrt(jnp.sum(x * x))[None]

    return jax.tree_util.tree_map_with_path(one, tree)


def named_rows(norms, layer_rows=None) -> dict:
    """``{leaf name: norms}`` on the host.  ``layer_rows`` picks, in layer
    order, the rows of period leaves that hold real layers (the program
    pads and reorders its period stack)."""
    out = {}
    for path, x in jax.tree_util.tree_flatten_with_path(norms)[0]:
        name = jax.tree_util.keystr(path)
        x = np.asarray(x, np.float64)
        if name.startswith("['periods']") and layer_rows is not None:
            x = x[np.asarray(layer_rows)]
        out[name] = x
    return out


def moved_rows(ref_grad: dict) -> dict:
    """Which leaf rows the reference's first gradient moves: those whose
    norm is at least a thousandth of the median row's.  Rows under that
    move by round-off alone under Adam."""
    med = float(np.median(np.concatenate(list(ref_grad.values()))))
    return {k: v >= 1e-3 * med for k, v in ref_grad.items()}


def row_gaps(prog: dict, ref: dict, keep: dict) -> dict:
    """``| |prog| - |ref| |`` of each kept row, against the larger of that
    row's reference norm and the median kept row's (inf where not finite,
    0 where not kept)."""
    med = float(np.median(np.concatenate([ref[k][keep[k]] for k in ref])))
    out = {}
    for k in ref:
        gap = np.abs(prog[k] - ref[k]) / np.maximum(ref[k], med)
        gap = np.where(np.isfinite(gap), gap, np.inf)
        out[k] = np.where(keep[k], gap, 0.0)
    return out


def worst_gap(prog: dict, ref: dict, keep: dict) -> tuple[float, str]:
    """The largest row gap, and the row it is in."""
    worst, where = 0.0, ""
    for k, gap in row_gaps(prog, ref, keep).items():
        i = int(np.argmax(gap))
        if gap[i] > worst:
            worst, where = float(gap[i]), f"{k}[{i}]"
    return worst, where


def median_gap(prog: dict, ref: dict, keep: dict) -> float:
    """The median row gap over the kept rows."""
    gaps = row_gaps(prog, ref, keep)
    return float(np.median(np.concatenate([gaps[k][keep[k]] for k in gaps])))
