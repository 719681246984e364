"""Model FLOPs per trained token, from a configuration's sizes.

Training counts 3x the forward pass (forward, then backward for inputs
and for weights); recomputation under remat is not counted.  A matmul
with an ``n``-parameter weight costs ``2n`` FLOPs per token forward.

What differs between model families sits in a module of its own,
``flops/<family>.py``, found by the configuration's ``reference`` name:

* ``matmul_params(m)``: parameters that take part in a matmul per token
  (the head included, the embedding lookup not);
* ``mixing_flops_forward(m, seq)``: per-token forward FLOPs of sequence
  mixing that has no weight;
* optionally ``scope_work(m, seq) -> {scope: (flops, bytes)}``: the
  training work per token, counted as above, of the real layers under one
  ``asteroid/*`` program scope, with the least HBM bytes it must move.
  Element-wise work is not counted.
"""

from __future__ import annotations

import importlib


def family(name: str):
    """The FLOP count of a model family (``flops/<name>.py``)."""
    module = f"{__name__}.{name}"
    try:
        return importlib.import_module(module)
    except ModuleNotFoundError as e:
        if e.name != module:
            raise
        raise ValueError(f"no FLOP count for model family {name!r}") from e


def matmul_params(name: str, m: dict) -> int:
    return family(name).matmul_params(m)


def mixing_flops_forward(name: str, m: dict, seq: int) -> float:
    return family(name).mixing_flops_forward(m, seq)


def train_flops_per_token(name: str, m: dict, seq: int) -> float:
    return 3.0 * (2.0 * matmul_params(name, m)
                  + mixing_flops_forward(name, m, seq))


def scope_work(name: str, m: dict, seq: int) -> dict | None:
    """The family's ``scope_work``, or None where it gives none."""
    work = getattr(family(name), "scope_work", None)
    return work(m, seq) if work else None
