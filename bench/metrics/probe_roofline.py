"""The probe kernels' share of the HBM roofline in the traced window: the
least bytes of the window's semi-join probes (``roofline.probe_least_bytes``
of every MSJ job that ran the Pallas backend) at the peak bandwidth, over
the kernels' device time."""
import probe_events
from roofline import bandwidth_share_pct, probe_least_bytes


def read(ctx):
    s = probe_events.seconds(ctx)
    if s is None:
        return None
    nbytes = sum(probe_least_bytes(j) for t in ctx.ticks for j in t.jobs
                 if j["backend"] == "pallas")
    return bandwidth_share_pct(nbytes, s, ctx.device_kind)
