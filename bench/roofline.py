"""Least work of the semi-join probe, and a kernel's share of its roofline.

The probe needs, at the least, to read every valid probe (Req) row and
every valid build (Assert) row once, in the packed message width of the
job's layout (3 int32 words when the key is its own fingerprint, i.e. one
key column; ``key_width + 3`` otherwise), and to write one word per probe
row.  Rows are the job's own count of valid received messages
(``recv_fwd``, probe and build together), never the capacity, the tile
padding or the 128-lane packing of a kernel.  The job's counts do not
split probe from build rows, so the output words are counted for the rows
that hit (``hits``), a lower bound of the probe rows: the least bytes, and
so the share, can only read low.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def message_words(key_width: int) -> int:
    return 3 if key_width <= 1 else key_width + 3


def probe_least_bytes(job: dict) -> int:
    """``job``: ``{"recv_fwd", "hits", "key_width"}`` of one MSJ job."""
    return 4 * (job["recv_fwd"] * message_words(job["key_width"]) + job["hits"])


def peaks(device_kind: str) -> dict:
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {PEAKS.name}")
    return table[device_kind]


def bandwidth_share_pct(nbytes: float, seconds: float, device_kind: str) -> float | None:
    """Percent of the HBM roofline: least time (bytes over peak bytes/s)
    over the measured time.  None where nothing was measured."""
    if seconds <= 0 or nbytes <= 0:
        return None
    return 100.0 * nbytes / peaks(device_kind)["hbm_bytes_per_s"] / seconds
