"""Share of the traced window in which the device is idle while the
host's innermost span is a ``host.sync``: a blocking device→host read
(``spans.py``)."""
import spans


def read(ctx):
    return spans.idle_pct(ctx, "sync")
