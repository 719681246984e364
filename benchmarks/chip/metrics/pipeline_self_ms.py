"""Device time per step of the pipeline's tick scan itself, in ms: the
ops under ``asteroid/pipeline`` and under no inner scope (input select, the
``outs`` update, the scan's saved residuals); the largest over the chips."""

from benchmarks.chip.metrics import scope_ms


def read(inputs):
    return scope_ms(inputs, "pipeline")
