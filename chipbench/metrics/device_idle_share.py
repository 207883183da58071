"""Share of the traced window in which no operation ran on a device,
averaged over the cell's devices."""


def read(ctx):
    tr = ctx["trace"]
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)
