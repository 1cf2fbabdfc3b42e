"""Share of the traced window in which no operation ran on the device,
in percent: 1 - (union of the device's op intervals) / window."""


def read(ctx):
    window = ctx.trace.window_s
    if window <= 0 or not ctx.trace.devices:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s / window)
