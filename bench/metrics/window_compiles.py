"""Executables that had to be built inside the window: backend compiles
plus loads from the persistent compilation cache, from JAX's monitoring
events.  Set-up warms every shape, so this should read 0."""


def read(ctx):
    return float(ctx.window_compiles)
