"""Device time of the MSJ probe's Pallas kernels per tick of the traced
window, in ms."""
import probe_events


def read(ctx):
    s = probe_events.seconds(ctx)
    if s is None or not ctx.ticks:
        return None
    return 1e3 * s / len(ctx.ticks)
