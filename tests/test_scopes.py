"""The train step's profiler scopes and its slot counter.

Every ``asteroid/<name>`` scope of ``repro.scopes`` has to reach the
*compiled* HLO's ``op_name`` metadata, through ``jit``, ``shard_map``, the
tick and period scans, remat and autodiff: that metadata is what names the
device ops of a profiler trace.  One stage compiles in this process; two
uneven stages need two host devices, so they compile in a subprocess that
sets the device count before JAX starts.
"""

import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

import jax
from jax.sharding import Mesh

from repro.configs import get_smoke_config
from repro.distributed.mesh import MeshPlan
from repro import scopes
from repro.runtime.pipeline import TrainSpec, slot_counts

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OP_NAME = re.compile(r'op_name="([^"]*)"')
SCOPE = re.compile(r"asteroid/(\w+)")

# compiles the smoke phi3 step for stages (2|1) on two host devices and
# prints the scope names its compiled HLO carries
TWO_STAGES = """
import json, sys
import jax
sys.path.insert(0, "tests")
from test_scopes import compiled_scopes
print(json.dumps(sorted(compiled_scopes(jax.devices()[:2], ((0, 2), (2, 3))))))
"""


def compiled_scopes(devices, stage_periods=None) -> set:
    """Innermost scope names of the ops in the compiled HLO of the smoke
    phi3 train step on a (1, len(devices)) mesh, one stage per device."""
    from repro.data import SyntheticLM
    from repro.runtime.train import build_train_step, init_train_state

    cfg = get_smoke_config("phi3-mini-3.8b")
    if stage_periods is not None:
        cfg = cfg.replace(n_layers=stage_periods[-1][1])
    n = len(devices)
    mesh = Mesh(np.array(devices).reshape(1, n), ("data", "model"))
    ts = build_train_step(cfg, mesh, global_batch=4, stage=n, n_micro=4,
                          stage_periods=stage_periods)
    params, opt_state = init_train_state(jax.random.PRNGKey(0), ts)
    batch = ts.shard_batch(SyntheticLM(cfg.vocab_size, 32).batch(0, 4))
    text = ts.step_fn.lower(params, opt_state, batch).compile().as_text()
    return {SCOPE.findall(op)[-1] for op in OP_NAME.findall(text)
            if SCOPE.search(op)}


@pytest.fixture
def no_compile_cache():
    """A persistent-cache entry compiled from the same program without
    scopes has the same key (metadata is not part of it) and would hand
    back text without them: compile afresh."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def test_one_stage_step_carries_its_scopes(no_compile_cache):
    # one stage has no last-stage redistribution; every other scope is there
    want = set(scopes.ALL) - {scopes.REDISTRIBUTE}
    assert compiled_scopes(jax.devices()[:1]) == want


def test_two_uneven_stages_carry_every_scope():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_ENABLE_COMPILATION_CACHE="false",
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=os.path.join(ROOT, "src"))
    proc = subprocess.run([sys.executable, "-c", TWO_STAGES], cwd=ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    found = json.loads(proc.stdout.strip().splitlines()[-1])
    assert found == sorted(scopes.ALL)


def spec(n_layers, stages, n_micro, stage_periods=None, double_buffer=False):
    cfg = get_smoke_config("phi3-mini-3.8b").replace(n_layers=n_layers)
    return TrainSpec(cfg=cfg, plan=MeshPlan(pod=1, data=1, stage=stages, tp=1),
                     n_micro=n_micro, stage_periods=stage_periods,
                     double_buffer=double_buffer)


def test_slot_counts_of_the_four_chip_plan():
    # the planner's Eq. 4 cut (3|2|2|1), padded to 3 slots, M + P - 1 = 11
    # ticks; lowering deploys 2|2|2|2 (test_lowering pins its 64/88)
    s = spec(8, 4, 8, ((0, 3), (3, 5), (5, 7), (7, 8)))
    assert slot_counts(s) == (64, 132)


def test_slot_counts_of_one_uniform_stage():
    real, computed = slot_counts(spec(4, 1, 8))
    assert real == computed == 32


@pytest.mark.parametrize("stages, n_periods", [(2, 4), (4, 8), (2, 3)])
def test_slot_counts_double_buffered_ticks(stages, n_periods):
    """The double-buffered scan runs M + 2(P - 1) ticks; a uniform split
    pads the period stack to a multiple of the stages."""
    m = 8
    k = -(-n_periods // stages)
    sync = slot_counts(spec(n_periods, stages, m))
    db = slot_counts(spec(n_periods, stages, m, double_buffer=True))
    assert sync == (n_periods * m, stages * k * (m + stages - 1))
    assert db == (n_periods * m, stages * k * (m + 2 * (stages - 1)))


def test_scope_names():
    assert len(set(scopes.ALL)) == len(scopes.ALL) == 10
    assert all(re.fullmatch(r"\w+", s) for s in scopes.ALL)
    assert scopes.SHARD_BATCH_SPAN == "asteroid.shard_batch"
