"""Share of the traced window in which a chip runs no op, in %: one minus
the union of its op intervals over the window, the largest over the
cell's chips."""


def read(inputs):
    summary = inputs.get("trace")
    if summary is None:
        return None
    return 100.0 * summary.idle_share()
