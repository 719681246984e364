"""Device time per step under the ``asteroid/experts`` scope, in ms: the
grouped matmuls of the held experts of each expert layer, forward, backward
and remat recompute; the union of its leaf ops, the largest over the
chips."""

from benchmarks.chip.metrics import scope_ms


def read(inputs):
    return scope_ms(inputs, "experts")
