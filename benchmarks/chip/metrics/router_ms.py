"""Device time per step under the ``asteroid/router`` scope, in ms: each
expert layer's router logits, top-k, balance loss, the sort of (token,
expert) pairs and the weighted combine, forward, backward and remat
recompute; the union of its leaf ops, the largest over the chips."""

from benchmarks.chip.metrics import scope_ms


def read(inputs):
    return scope_ms(inputs, "router")
