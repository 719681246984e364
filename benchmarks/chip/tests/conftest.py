"""Benchmark tests run on the CPU, with four host devices for the
four-chip cell:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests
"""

import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[3]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402

jax.config.update("jax_num_cpu_devices", 4)
# initialise the backend now, so that no later selection of fewer devices
# can shrink it
assert len(jax.devices()) == 4
