"""Share of the MLP's roofline, in %: the least time the chips need for the
MLP work they compute in a step, over the device time of the
``asteroid/mlp`` scope summed over the chips.

The work is the family's ``scope_work["mlp"]`` (``flops/``) for the step's
tokens: model FLOPs, 3x the forward pass with no remat recompute, and a
lower bound on the HBM bytes, scaled by the period slots the pipeline
computes over its real ones (``counters``).  On several chips the tick scan
also runs the MLP in its fill and drain slots; counted so, the share reads
the MLP kernels' own efficiency, and ``slot_use_pct`` the slots that do no
real work.  The least time is the larger of FLOPs over the bf16 peak and
bytes over HBM bandwidth.
"""


def read(inputs):
    work = (inputs.get("scope_work") or {}).get("mlp")
    seconds = (inputs.get("scope_s") or {}).get("mlp")
    slots = inputs.get("counters") or {}
    peaks = inputs.get("peaks")
    if not work or not seconds or not sum(seconds) or not peaks \
            or not slots.get("slots_real"):
        return None
    flops, nbytes = work
    tokens = (inputs["global_batch"] * inputs["seq"]
              * slots["slots_computed"] / slots["slots_real"])
    least = tokens * max(flops / peaks["bf16_flops_per_s"],
                         nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / sum(seconds)
