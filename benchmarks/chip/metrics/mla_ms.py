"""Device time per step under the ``asteroid/mla`` scope, in ms: the latent
attention of each layer (projections, YaRN rope, the chunked score and
value blocks; innermost inside ``attention``), forward, backward and remat
recompute; the union of its leaf ops, the largest over the chips."""

from benchmarks.chip.metrics import scope_ms


def read(inputs):
    return scope_ms(inputs, "mla")
