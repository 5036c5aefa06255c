"""Share of the traced window in which no operation ran on a device,
averaged over the cell's devices (1 - union of op intervals / window)."""


def read(summary):
    return 1.0 - summary.busy_s / summary.window_s
