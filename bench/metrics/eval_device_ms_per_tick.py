"""Device time of the executables launched under an ``eval.*`` span
(EVAL's shuffle and reduce, publishing an EVAL job's outputs), per tick
of the traced window, in ms."""
import spans


def read(ctx):
    return spans.device_ms_per_tick(ctx, "eval")
