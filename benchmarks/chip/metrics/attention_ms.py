"""Device time per step under the ``asteroid/attention`` scope, in ms: the
attention of each layer (projections, rope, the chunked score and value
blocks), forward, backward and remat recompute; the union of its leaf ops,
the largest over the chips."""

from benchmarks.chip.metrics import scope_ms


def read(inputs):
    return scope_ms(inputs, "attention")
