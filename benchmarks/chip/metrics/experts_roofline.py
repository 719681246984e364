"""Share of the held experts' roofline, in %: the least time the chips need
for the expert rows they computed in a step, over the device time of the
``asteroid/experts`` scope summed over the chips.

The rows are the step's ``expert_rows`` metric (the (token, expert) pairs
the held experts computed, over every expert layer and micro-batch).  Each
row is a SwiGLU of ``d_model x moe.d_ff`` three ways, 3x forward with no
remat recompute.  The bytes are a lower bound: the held experts' weights
read in the forward pass, read in the backward pass and their gradient
written, once each per step.  The least time is the larger of FLOPs over
the bf16 peak and bytes over HBM bandwidth.
"""

BYTES = {"float32": 4, "bfloat16": 2}


def read(inputs):
    rows = (inputs.get("step_metrics") or {}).get("expert_rows")
    seconds = (inputs.get("scope_s") or {}).get("experts")
    peaks, m = inputs.get("peaks"), inputs.get("model") or {}
    e = m.get("moe")
    if not rows or not seconds or not sum(seconds) or not peaks or not e:
        return None
    matrices = 3 * m["d_model"] * e["d_ff"]
    flops = rows * 3.0 * 2.0 * matrices
    nbytes = (3.0 * m["n_layers"] * e["n_experts"] * matrices
              * BYTES[m["param_dtype"]])
    least = max(flops / peaks["bf16_flops_per_s"],
                nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / sum(seconds)
