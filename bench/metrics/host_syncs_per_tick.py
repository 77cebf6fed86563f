"""Blocking device→host reads per tick of the traced window: the
program's ``host.sync`` spans over the window's ticks."""
import spans


def read(ctx):
    if ctx.trace is None or not ctx.ticks:
        return None
    found = spans.program_spans(ctx.trace)
    if not found:
        return None
    return sum(name == "host.sync" for name, _, _ in found) / len(ctx.ticks)
