"""The launchers' in-process entry points (``main(argv)``), device
selection and the placement of the persistent compile cache."""

import math

import jax
import jax.numpy as jnp
import pytest

from repro.launch.devices import CACHE_DIR, place_compile_cache, select_devices


@pytest.fixture
def restore_cache_config():
    """Entry points place the persistent cache; put the test process's
    setting back so later tests do not write to it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)
    cc.reset_cache()


def test_cache_defaults_to_repo_dir(monkeypatch, restore_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    place_compile_cache()
    assert jax.config.jax_compilation_cache_dir == str(CACHE_DIR)
    assert CACHE_DIR.parent.joinpath("pyproject.toml").exists()


def test_cache_env_dir_is_left_to_jax(monkeypatch, tmp_path,
                                      restore_cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    place_compile_cache()
    assert jax.config.jax_compilation_cache_dir == before


def test_select_devices_takes_first_n_and_refuses_more(restore_cache_config):
    devs = jax.devices()
    assert select_devices(1) == devs[:1]
    assert select_devices(0) == devs
    with pytest.raises(SystemExit, match="devices are present"):
        select_devices(len(devs) + 1)


def test_train_main_plan_matches_reference_loss(restore_cache_config):
    """``launch.train.main(argv)`` through the planner: finite per-step
    losses, the first step's loss equals the plain model's loss on the
    same seed and batch, and the second step's equals the plain model's
    after one gradient and optimizer step."""
    from repro.configs import get_smoke_config
    from repro.data import SyntheticLM
    from repro.launch import train
    from repro.models.model import init_model, loss_fn

    res = train.main(["--smoke", "--plan", "--env", "v5e", "--devices", "1",
                      "--steps", "2", "--seq", "32", "--global-batch", "4",
                      "--log-every", "1"])
    assert [s for s, _, _ in res["log"]] == [0, 1]
    assert all(math.isfinite(loss) and t > 0 for _, loss, t in res["log"])
    assert math.isfinite(res["loss"]) and res["tok_s"] > 0

    cfg = get_smoke_config("phi3-mini-3.8b")
    ds = SyntheticLM(cfg.vocab_size, 32)
    b0, b1 = ({k: jnp.asarray(v) for k, v in ds.batch(i, 4).items()}
              for i in (0, 1))
    params = init_model(jax.random.PRNGKey(0), cfg)
    (ref0, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(
        params, b0, cfg)
    opt = res["optimizer"]
    params, _ = opt.update(grads, opt.init(params), params)
    ref1, _ = loss_fn(params, b1, cfg)
    assert abs(res["log"][0][1] - float(ref0)) < 1e-5
    assert abs(res["log"][1][1] - float(ref1)) < 1e-5


def test_serve_main_lockstep_decode(restore_cache_config):
    from repro.launch import serve

    res = serve.main(["--smoke", "--devices", "1", "--batch", "2",
                      "--prompt-len", "3", "--gen", "4"])
    assert res["logits_finite"]
    assert res["tokens"].shape == (7, 2)
    assert res["tok_s"] > 0
