"""Collective time per step that no compute hides, in ms: the part of the
collective ops' intervals in which no other op runs on that chip, the
largest over the chips, over the traced steps."""


def read(inputs):
    summary = inputs.get("trace")
    if summary is None or not inputs.get("traced_steps"):
        return None
    worst = max(c.exposed_collective_ns for c in summary.chips)
    return worst / 1e6 / inputs["traced_steps"]
