"""Requests completed over the window: every request of every tick, over
the time from the window's start to the last completion (the tick in
flight when time is up is finished)."""


def read(ctx):
    n = sum(len(t.latencies) for t in ctx.ticks)
    if n == 0 or ctx.window_s <= 0:
        return None
    return n / ctx.window_s
