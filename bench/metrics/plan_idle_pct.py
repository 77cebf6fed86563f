"""Share of the traced window in which the device is idle while the host
is in a service span other than ``svc.execute`` (submit, register,
fusion, cache lookup, planning, trimming, verification, insertion,
scattering outputs), innermost on the host's stack (``spans.py``)."""
import spans


def read(ctx):
    return spans.idle_pct(ctx, "plan")
