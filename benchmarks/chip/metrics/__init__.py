"""Per-layer metric readers: ``read(inputs) -> float | None``.

``inputs`` holds the job's ``layer_inputs`` and, from the harness, the
trace ``Summary`` (``trace``) and the chip's ``peaks``.  The train job's
inputs are:

* ``tok_s``, ``chips``, ``step_s`` (the window's seconds per step),
  ``plan_latency_s``, ``traced_steps`` and ``flops_per_token``;
* ``scope_s``: seconds per traced step of each program scope, a list with
  one entry per chip (``trace.Summary.scope_s``);
* ``counters``: what the program counts about the built step;
* ``step_metrics``: the mean over the traced steps of each scalar the
  step returns as its ``metrics``;
* ``model``, ``family``, ``seq``, ``global_batch``: the configuration's
  sizes, its model family and the traffic's step;
* ``scope_work``: the family's work per token by scope (``flops/``), or
  None.

A reader that finds nothing to read returns ``None`` and the metric is left
out of the line.
"""


def scope_ms(inputs, scope: str):
    """Milliseconds per step under one program scope, the largest over the
    chips, or None where the trace has no such scope."""
    per_chip = (inputs.get("scope_s") or {}).get(scope)
    return 1e3 * max(per_chip) if per_chip else None
