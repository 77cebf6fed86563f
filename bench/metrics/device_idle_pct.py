"""Share of the traced window in which no operation ran on the device:
100 · (1 − busy / window), busy the union of the device's op intervals."""


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices or ctx.window_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.busy_s / ctx.window_s)
