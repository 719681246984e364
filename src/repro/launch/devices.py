"""Device selection and compile-cache placement shared by the entry points.

Call ``select_devices`` at the top of an entry point's ``main``, before any
JAX computation: forcing host devices on the CPU backend only works before
JAX initializes its backends, and the persistent compile cache must be in
place before the first compile.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax
from jax._src import xla_bridge

REPO_ROOT = Path(__file__).resolve().parents[3]
CACHE_DIR = REPO_ROOT / ".jax_cache"


def place_compile_cache() -> None:
    """Keep compiled programs in ``<repo>/.jax_cache`` unless
    ``JAX_COMPILATION_CACHE_DIR`` names a directory, which JAX then reads
    itself.  The path is fixed, so the next run in the same checkout finds
    what this one wrote."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))


def select_devices(n: int = 0) -> list:
    """The first ``n`` of ``jax.devices()`` (all of them when ``n`` is 0).

    On the CPU backend ``n`` host devices are forced first, so a CPU run
    emulates an ``n``-device mesh; on an accelerator nothing is emulated.
    Fewer devices than asked for is an error, never a smaller mesh."""
    place_compile_cache()
    if n and not xla_bridge.backends_are_initialized():
        jax.config.update("jax_num_cpu_devices", n)
    devs = jax.devices()
    if n > len(devs):
        raise SystemExit(f"--devices {n}: only {len(devs)} "
                         f"{devs[0].platform} devices are present")
    return devs[:n] if n else devs
