"""The model-FLOP count against the program's own parameter count."""

import pytest

from benchmarks.chip import flops, harness
from benchmarks.chip.jobs.train import program_config

CONFIGS = ["phi3-mini-l4", "phi3-mini-l8", "rwkv6-7b-l2"]


def load(name):
    return harness.load_json(harness.HERE / "configs" / f"{name}.json")


@pytest.mark.parametrize("name", CONFIGS)
def test_matmul_params_match_param_count(name):
    config = load(name)
    m = config["model"]
    cfg = program_config(config, m)
    # param_count = matmul weights + the embedding + two norms per layer
    want = (cfg.param_count() - cfg.vocab_size * cfg.d_model
            - 2 * cfg.d_model * cfg.n_layers)
    assert flops.matmul_params(config["reference"], m) == want


@pytest.mark.parametrize("name", CONFIGS)
def test_train_flops_per_token(name):
    config = load(name)
    m, fam = config["model"], config["reference"]
    cfg = program_config(config, m)
    seq = 2048
    dense = 6 * (cfg.param_count() - cfg.vocab_size * cfg.d_model
                 - 2 * cfg.d_model * cfg.n_layers)
    if fam == "dense_swiglu":
        a = cfg.attn
        mixing = 6 * cfg.n_layers * a.n_heads * a.head_dim * (seq + 1)
    else:
        mixing = 12 * cfg.n_layers * cfg.d_model * cfg.rwkv.head_dim
    assert flops.train_flops_per_token(fam, m, seq) == pytest.approx(
        dense + mixing, rel=1e-12)


def test_phi3_mini_l4_in_gflop():
    """6 x 551.4 M matmul params + 0.151 GFLOP of causal attention."""
    got = flops.train_flops_per_token("dense_swiglu",
                                      load("phi3-mini-l4")["model"], 2048)
    assert got == pytest.approx(3.45998e9, rel=1e-5)


def test_unknown_family_is_an_error():
    with pytest.raises(ValueError):
        flops.matmul_params("mamba", load("phi3-mini-l4")["model"])


def test_family_file_is_found_by_name():
    from benchmarks.chip.flops import dense_swiglu, rwkv6

    assert flops.family("dense_swiglu") is dense_swiglu
    assert flops.family("rwkv6") is rwkv6


@pytest.mark.parametrize("count", [
    lambda m: flops.matmul_params("mamba", m),
    lambda m: flops.mixing_flops_forward("mamba", m, 2048),
    lambda m: flops.train_flops_per_token("mamba", m, 2048),
    lambda m: flops.scope_work("mamba", m, 2048),
])
def test_unknown_family_raises(count):
    with pytest.raises(ValueError, match="mamba"):
        count(load("phi3-mini-l4")["model"])


def test_mlp_scope_work():
    """phi3-mini-l4's MLP: 2.97e13 model FLOPs in a step of 8 x 2048
    tokens; the family without ``scope_work`` gives None."""
    m = load("phi3-mini-l4")["model"]
    mlp_flops, mlp_bytes = flops.scope_work("dense_swiglu", m, 2048)["mlp"]
    # 3 passes x 2 FLOPs x 4 layers x 3 matrices of 3072 x 8192
    assert mlp_flops == 3 * 2 * 4 * 3 * 3072 * 8192
    assert mlp_flops * 8 * 2048 == pytest.approx(2.97e13, rel=1e-3)
    assert mlp_bytes == 3 * 4 * 2 * 3072 * 4
    assert flops.scope_work("rwkv6", load("rwkv6-7b-l2")["model"],
                            2048) is None
