"""Shared by the probe readers: the device time of the MSJ probe's Pallas
kernels, as the TPU trace names them (PERF.md, Layers).

The kernels carry no ``name=``, and their operation's HLO text does not
name them (``%closed_call.<n> = ... fusion(...), kind=kCustom``).  Its
``tf_op`` stat does: the JAX name stack the kernel was traced under,
``jit(probe_bucketed_blocked)/while/body/closed_call/pallas_call`` when
the probe stage calls the vmapped kernel eagerly, and the same under the
enclosing function's name when the kernel runs inside a larger jit.
"""

#: name stack of the probe kernels' operations (``tf_op``)
PATTERN = r"probe(_bucketed)?_blocked\).*/pallas_call"


def seconds(ctx):
    """Device seconds of the probe kernels in the traced window, or None
    when no MSJ job ran the Pallas backend or the trace has no device.
    Raises when a job did and the device's trace holds no probe kernel
    operation: the kernel has then left the readers' sight."""
    if ctx.trace is None or not ctx.trace.devices:
        return None
    from trace_reduce import kernel_s

    s, count = kernel_s(ctx.trace, PATTERN)
    pallas = sum(j["backend"] == "pallas" for t in ctx.ticks for j in t.jobs)
    if pallas and not count:
        raise RuntimeError(
            f"{pallas} Pallas MSJ jobs ran in the traced window, but no device "
            f"operation's name stack matches {PATTERN!r}")
    return s if count else None
