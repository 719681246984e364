"""DeepSeek-V2-Lite's mechanisms at small sizes on the CPU: YaRN rope, latent
attention without q-LoRA, the dropless expert layer at a chip's share, the
``seq_aux`` balance loss, the leading dense layer through the pipeline, and
the program against the benchmark's plain float32 reference.

The plain reference (``benchmarks/chip/reference/deepseek_v2.py``) imports
nothing of the program; the CPU computes both in exact float32, so the
program has to match it to float32 round-off.
"""

import dataclasses
import json
import math
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import scopes
from repro.configs import get_config, get_smoke_config
from repro.core.lowering import period_layers, snap_to_periods
from repro.core.profiler import LayerTable
from repro.models.mlp import mlp
from repro.models.moe import MoEConfig, _router, init_moe, moe
from repro.models.rotary import YaRN, rope_cos_sin, rope_freqs

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks.chip import flops, harness  # noqa: E402
from benchmarks.chip.jobs.train import program_config  # noqa: E402
from benchmarks.chip.reference import common, deepseek_v2  # noqa: E402
from test_scopes import compiled_scopes, no_compile_cache  # noqa: E402,F401

ARCH = "deepseek-v2-lite-16b"
CONFIG = harness.load_json(harness.HERE / "configs" / "deepseek-v2-lite-l5.json")


# ---------------------------------------------------------------------------
# YaRN
# ---------------------------------------------------------------------------


def test_yarn_frequencies_match_the_closed_form():
    """d = 64, b = 10000: the ramp runs over channel pairs [10, 23]."""
    y = get_config(ARCH).attn.rope_scaling
    d, b = 64, 10000.0
    corr = lambda r: d * math.log(4096 / (2 * math.pi * r)) / (2 * math.log(b))
    assert y.ramp_bounds(d, b) == (math.floor(corr(32)), math.ceil(corr(1))) \
        == (10, 23)
    i = np.arange(32, dtype=np.float64)
    ramp = np.clip((i - 10) / 13, 0, 1)
    base = b ** (-2 * i / d)
    want = base / 40 * ramp + base * (1 - ramp)
    np.testing.assert_allclose(np.asarray(rope_freqs(d, b, y)), want,
                               rtol=2e-6)


def test_yarn_scales():
    """cos and sin keep their size (mscale = mscale_all_dim); the softmax
    scale is 192^-0.5 (0.1 * 0.707 * ln 40 + 1)^2."""
    cfg = get_config(ARCH).attn
    assert cfg.rope_scaling.cos_sin_scale == 1.0
    assert cfg.softmax_scale(192) == pytest.approx(0.114721, abs=5e-7)
    assert cfg.softmax_scale(192) == pytest.approx(
        192 ** -0.5 * (0.1 * 0.707 * math.log(40) + 1) ** 2, rel=1e-12)


def test_no_rope_scaling_keeps_plain_rope():
    pos = jnp.arange(64)
    cos, sin = rope_cos_sin(pos, 32, 10000.0)
    freqs = 1.0 / (10000.0 ** (jnp.arange(0, 32, 2, dtype=jnp.float32) / 32))
    ang = pos[:, None].astype(jnp.float32) * freqs
    assert bool(jnp.all(cos == jnp.cos(ang))) and bool(jnp.all(sin == jnp.sin(ang)))
    yc, _ = rope_cos_sin(pos, 32, 10000.0, YaRN())
    assert not bool(jnp.all(yc == cos))


# ---------------------------------------------------------------------------
# The expert layer
# ---------------------------------------------------------------------------


def setup(key, e=16, held=None, k=2, d=32, f=16, shared=2, **kw):
    cfg = MoEConfig(n_experts=held or e, n_routed=e, top_k=k, d_ff=f,
                    n_shared_experts=shared, norm_topk_prob=False,
                    aux_loss="seq", aux_loss_weight=0.001, **kw)
    full = init_moe(key, d, dataclasses.replace(cfg, n_experts=e))
    return cfg, full


def test_the_shares_of_a_layer_add_up_to_the_uncut_layer():
    """Over the 8 shares of a 64-expert layer (8 held each), the routed
    parts summed, with the shared experts counted once, equal the uncut
    reference layer."""
    key = jax.random.PRNGKey(0)
    cfg, full = setup(key, e=64, held=8, k=6)
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, 16, 32))
    total = mlp(full["shared"], x)
    rows = 0.0
    for s in range(8):
        part = {"router": full["router"],
                "experts": jax.tree.map(lambda w: w[8 * s: 8 * s + 8],
                                        full["experts"])}
        out, _, r = moe(part, x, dataclasses.replace(cfg, first_expert=8 * s))
        total, rows = total + out, rows + float(r)
    assert rows == 2 * 16 * 6
    m = {"moe": {"n_experts": 64, "n_routed": 64, "first_expert": 0,
                 "top_k": 6, "norm_topk_prob": False,
                 "aux_loss_weight": 0.001}}
    with jax.default_matmul_precision("highest"):
        want, _ = deepseek_v2._experts(full, x, m, "float32")
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_nothing_is_dropped_when_every_token_picks_the_same_experts():
    """A router of zeros scores every expert alike, so every token picks
    experts 0 and 1 (ties go to the lower index): all 2 x 64 x 2 pairs land
    on two of the eight held experts, and each is computed."""
    key = jax.random.PRNGKey(1)
    cfg, full = setup(key, e=16, held=8, k=2, shared=0)
    params = {"router": jnp.zeros_like(full["router"]),
              "experts": jax.tree.map(lambda w: w[:8], full["experts"])}
    x = jax.random.normal(key, (2, 64, 32))
    out, _, rows = moe(params, x, cfg)
    assert float(rows) == 2 * 64 * 2
    ex = params["experts"]

    def expert(i, t):
        return (jax.nn.silu(t @ ex["gate"][i]) * (t @ ex["up"][i])) @ ex["down"][i]

    want = (expert(0, x) + expert(1, x)) / 16    # softmax weights of 1/16
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_unnormalised_top_k_weights_are_the_softmax_probabilities():
    key = jax.random.PRNGKey(2)
    cfg, full = setup(key, e=16, k=4)
    x = jax.random.normal(key, (32, 32))
    w, e, _ = _router(full, x, cfg, 1)
    probs = jax.nn.softmax(x @ full["router"], axis=-1)
    np.testing.assert_allclose(np.asarray(w),
                               np.asarray(jnp.take_along_axis(probs, e, -1)),
                               rtol=1e-6)
    wn, en, _ = _router(full, x, dataclasses.replace(cfg, norm_topk_prob=True), 1)
    assert bool(jnp.all(en == e))
    np.testing.assert_allclose(np.asarray(wn.sum(-1)), 1.0, rtol=1e-6)


def test_seq_aux_on_a_hand_built_routing():
    """E = 4, K = 2, one sequence of 4 tokens, one-hot on experts 0, 0, 1,
    2 with a router of 10 I: each token's second choice is the lowest other
    expert.  Choices of experts 0..3: 4, 3, 1, 0, so f = (2, 1.5, 0.5, 0);
    with a = e^10 / (e^10 + 3), b = 1 / (e^10 + 3) the mean scores are
    ((2a + 2b) / 4, (a + 3b) / 4, (a + 3b) / 4, b), and
    aux = alpha (1.5 a + 2.5 b)."""
    cfg = MoEConfig(n_experts=4, top_k=2, d_ff=8, norm_topk_prob=False,
                    aux_loss="seq", aux_loss_weight=0.001)
    params = {"router": 10.0 * jnp.eye(4)}
    x = jax.nn.one_hot(jnp.array([0, 0, 1, 2]), 4)
    _, e, aux = _router(params, x, cfg, 1)
    assert e.tolist() == [[0, 1], [0, 1], [1, 0], [2, 0]]
    a = math.exp(10) / (math.exp(10) + 3)
    b = 1 / (math.exp(10) + 3)
    assert float(aux) == pytest.approx(0.001 * (1.5 * a + 2.5 * b), rel=1e-6)
    # two such sequences: the mean over sequences, the same value
    _, _, aux2 = _router(params, jnp.concatenate([x, x]), cfg, 2)
    assert float(aux2) == pytest.approx(float(aux), rel=1e-6)


# ---------------------------------------------------------------------------
# Sizes
# ---------------------------------------------------------------------------


def test_the_cut_counts_the_held_share():
    """The cell: 81.0 M dense layer, 4 x 100.4 M expert layers, 2 x 26.2 M
    embedding and head; per token the held experts do 6 x 8 / 64 of an
    expert's work."""
    cfg = program_config(CONFIG, CONFIG["model"])
    assert cfg.first_k_dense == 1 and cfg.n_layers == 4
    assert cfg.moe.n_experts == 8 and cfg.moe.router_width == 64
    assert cfg.param_count() == pytest.approx(535.0e6, rel=1e-3)
    spec = cfg.pattern[0]
    expert = 3 * 2048 * 1408
    assert (cfg.layer_param_count(spec) - cfg.layer_active_param_count(spec)
            == 8 * expert - 6 * 8 * expert // 64)


def test_flops_count_of_the_six_layer_cut():
    """One dense and five expert layers: 295,632,896 matmul parameters per
    token, 2.15 GFLOP a token at seq 4096."""
    m = dict(CONFIG["model"], n_layers=5)
    assert flops.matmul_params("deepseek_v2", m) == 295_632_896
    assert flops.train_flops_per_token("deepseek_v2", m, 4096) == \
        pytest.approx(2.15e9, rel=3e-3)
    cfg = program_config(CONFIG, m)
    # matmul weights: the parameters a token touches but the embedding and
    # the two norms of each layer, the routed experts at the held share
    norms = 2 * 2048 * (cfg.n_layers + cfg.first_k_dense)
    assert flops.matmul_params("deepseek_v2", m) == \
        cfg.active_param_count() - 12800 * 2048 - norms


def test_dense_prefix_sits_after_the_embedding_in_the_layer_table():
    cfg = get_smoke_config(ARCH)
    table = LayerTable.from_model_config(cfg, 64)
    names = [l.name for l in table.layers]
    assert names[:3] == ["embed", "dense0", "attn1"] and names[-1] == "head"
    assert len(names) == 1 + cfg.first_k_dense + cfg.n_layers + 1
    # lowering: a cut after the first period puts the dense layer on stage 0
    cuts = snap_to_periods([(0, 3), (3, 4)], cfg.n_layers, 1, 2, lead=1)
    assert cuts == ((0, 1), (1, 2))
    assert period_layers(cuts, 1, table.L, lead=1) == ((0, 3), (3, 5))


# ---------------------------------------------------------------------------
# The program against the plain reference
# ---------------------------------------------------------------------------


def test_program_matches_the_reference_at_float32():
    """The rehearsal sizes of the cell (8 of 16 experts held, top-2): the
    program's loss (cross entropy plus the balance loss) and every
    gradient leaf against the reference's, on the reference's weights.
    Both compute in exact float32 on the CPU and route identically, so they
    agree to float32 round-off (1e-5 relative)."""
    cell = harness.Cell.load("deepseek-v2-lite.train.1chip").rehearsal()
    m = cell.config["model"]
    cfg = program_config(cell.config, m)
    from repro.models.model import loss_fn

    params = deepseek_v2.init(common.Draw(jax.random.PRNGKey(3)), m)
    tokens = jax.random.randint(jax.random.PRNGKey(4), (2, 64), 0, m["vocab_size"])
    with jax.default_matmul_precision("highest"):
        got, g_got = jax.value_and_grad(
            lambda p: loss_fn(p, {"tokens": tokens}, cfg)[0])(params)
        want, g_want = common.blocked_loss_and_grad(
            lambda p, t: deepseek_v2.loss(p, t, m), params, tokens)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    flat_got = jax.tree_util.tree_flatten_with_path(g_got)[0]
    flat_want = dict(jax.tree_util.tree_flatten_with_path(g_want)[0])
    assert len(flat_got) == len(flat_want)
    for path, g in flat_got:
        w = flat_want[path]
        scale = float(jnp.max(jnp.abs(w))) + 1e-12
        assert float(jnp.max(jnp.abs(g - w))) / scale < 1e-4, \
            jax.tree_util.keystr(path)


def test_step_carries_the_latent_attention_and_expert_scopes(no_compile_cache):
    found = compiled_scopes(jax.devices()[:1], arch=ARCH)
    # every op of the latent attention is innermost in ``mla``; one stage
    # has no last-stage redistribution
    assert found == (set(scopes.ALL) | set(scopes.MODEL_SPECIFIC)) - {
        scopes.ATTENTION, scopes.REDISTRIBUTE}
    assert len(set(scopes.ALL + scopes.MODEL_SPECIFIC)) == 13


# ---------------------------------------------------------------------------
# The leading dense layer through a two-stage pipeline
# ---------------------------------------------------------------------------

# Runs the smoke DeepSeek-V2-Lite (one dense layer, two expert layers) on
# one and on two pipeline stages over two host devices.  A callback inside
# the dense layers records the stage that runs them.
TWO_STAGES = """
import json
import numpy as np
import jax
from jax import lax
from jax.sharding import Mesh
from repro.configs import get_smoke_config
from repro.data import SyntheticLM
from repro.runtime import pipeline
from repro.runtime.train import build_train_step, init_train_state

calls = []
real = pipeline.apply_dense_prefix

def counted(*a, **kw):
    jax.debug.callback(lambda s: calls.append(int(s)), lax.axis_index("stage"))
    return real(*a, **kw)

pipeline.apply_dense_prefix = counted
cfg = get_smoke_config("deepseek-v2-lite-16b")
batch = SyntheticLM(cfg.vocab_size, 32).batch(0, 4)
out = {}
for n in (1, 2):
    mesh = Mesh(np.array(jax.devices()[:n]).reshape(1, n), ("data", "model"))
    ts = build_train_step(cfg, mesh, global_batch=4, stage=n, n_micro=2)
    params, _ = init_train_state(jax.random.PRNGKey(0), ts)
    calls.clear()
    (loss, metrics), grads = ts.grad_fn(params, ts.shard_batch(batch))
    jax.effects_barrier()
    g = jax.tree.map(lambda x: float(np.abs(np.asarray(x)).sum()),
                     grads["dense_prefix"])
    out[n] = {"loss": float(loss), "rows": float(metrics["expert_rows"]),
              "stages": sorted(set(calls)), "grad": jax.tree.leaves(g)}
print(json.dumps(out))
"""


def test_two_stages_run_the_dense_layer_on_the_first_only():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_ENABLE_COMPILATION_CACHE="false",
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=os.path.join(ROOT, "src"))
    proc = subprocess.run([sys.executable, "-c", TWO_STAGES], cwd=ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    one, two = out["1"], out["2"]
    assert two["loss"] == pytest.approx(one["loss"], rel=1e-6)
    assert two["rows"] == one["rows"] == 2 * 4 * 32 * 2
    np.testing.assert_allclose(two["grad"], one["grad"], rtol=1e-4)
    assert one["stages"] == two["stages"] == [0]
