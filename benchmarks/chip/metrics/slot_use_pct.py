"""Share of the period slots a step computes that are real, in %: the
model's periods times the micro-batches, over what the pipeline's tick scan
computes on all stages (padding and fill and drain included)."""


def read(inputs):
    c = inputs.get("counters") or {}
    if not c.get("slots_computed"):
        return None
    return 100.0 * c["slots_real"] / c["slots_computed"]
