"""Bytes the MSJ and EVAL jobs exchanged, per tick (``Report.bytes_shuffled``,
in MB of 10^6 bytes, mean over the window's ticks)."""


def read(ctx):
    if not ctx.ticks:
        return None
    return sum(t.bytes_shuffled for t in ctx.ticks) / len(ctx.ticks) / 1e6
