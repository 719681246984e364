"""Device time per step under the ``asteroid/optimizer`` scope, in ms: the
optimizer update of every parameter; the union of its leaf ops, the largest
over the chips."""

from benchmarks.chip.metrics import scope_ms


def read(inputs):
    return scope_ms(inputs, "optimizer")
