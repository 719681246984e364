"""How far the planner's predicted round latency is from the measured
step, in % of the measured step: |predicted - measured| / measured."""


def read(inputs):
    predicted, measured = inputs.get("plan_latency_s"), inputs.get("step_s")
    if not predicted or not measured:
        return None
    return 100.0 * abs(predicted - measured) / measured
