"""Share of the traced window in which no operation ran on the device
(one minus the union of the operation intervals over the window),
averaged over the chips."""


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s / ctx.trace.window_s)
