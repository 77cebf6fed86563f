"""Share of the traced window in which the device is idle while the host
is inside ``svc.execute`` (the executor, a job attempt, an MSJ or EVAL
phase, publishing outputs) and not in a ``host.sync`` read
(``spans.py``)."""
import spans


def read(ctx):
    return spans.idle_pct(ctx, "dispatch")
