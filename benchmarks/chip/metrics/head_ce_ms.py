"""Device time per step under the ``asteroid/head_ce`` scope, in ms: the final
norm, the vocabulary-parallel head and the chunked cross-entropy with its
loss reductions; the union of its leaf ops, the largest over the chips."""

from benchmarks.chip.metrics import scope_ms


def read(inputs):
    return scope_ms(inputs, "head_ce")
