"""Device time per step under the ``asteroid/mlp`` scope, in ms: the dense
SwiGLU MLP of each layer, forward, backward and remat recompute; the union
of its leaf ops, the largest over the chips."""

from benchmarks.chip.metrics import scope_ms


def read(inputs):
    return scope_ms(inputs, "mlp")
