"""Share of the traced window in which no op ran on the device, averaged
over the chips: 1 - (union of op intervals / window)."""


def read(ctx):
    return 100.0 * (1.0 - ctx.trace.mean_busy_s() / ctx.trace.window_s)
