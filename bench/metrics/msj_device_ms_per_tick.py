"""Device time of the executables launched under an ``msj.*`` span (count
exchange, bloom, forward shuffle, transfer, probe, scatter, publishing
an MSJ job's outputs), per tick of the traced window, in ms."""
import spans


def read(ctx):
    return spans.device_ms_per_tick(ctx, "msj")
