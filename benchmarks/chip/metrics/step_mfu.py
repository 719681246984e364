"""Model FLOP utilisation of the whole train step, in %.

Model FLOPs per token (``flops/``: forward and backward, no remat
recompute) times tokens per second, over the chips' summed bf16 peak.
"""


def read(inputs):
    peaks = inputs.get("peaks")
    if not peaks or not inputs.get("flops_per_token"):
        return None
    return (100.0 * inputs["flops_per_token"] * inputs["tok_s"]
            / (inputs["chips"] * peaks["bf16_flops_per_s"]))
