"""Peak device memory in use after the window (the allocator's
``peak_bytes_in_use``), in GB of 10^9 bytes."""


def read(ctx):
    if ctx.peak_bytes <= 0:
        return None
    return ctx.peak_bytes / 1e9
