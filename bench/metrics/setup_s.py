"""Process start to window start: data from the seed, placement, warm-up
ticks and whatever they compile or load from the compile cache."""


def read(ctx):
    return ctx.setup_s
