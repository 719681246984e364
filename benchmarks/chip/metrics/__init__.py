"""Per-layer metric readers: ``read(inputs) -> float | None``.

``inputs`` holds the job's ``layer_inputs`` (tokens/s, chips, step
seconds, the plan's predicted latency, model FLOPs per token), the trace
``Summary`` and the chip's ``peaks``.  A reader that finds nothing to read
returns ``None`` and the metric is left out of the line.
"""
