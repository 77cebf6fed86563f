"""Reduce a profiler trace (``.xplane.pb``) to device time.

Events are read with ``jax.profiler.ProfileData``.  Device planes are
those named ``/device:TPU:<n>``.  Their ``XLA Ops`` line holds one event
per HLO operation, named by its HLO text; their ``XLA Modules`` line one
event per executable run, named ``jit_<function>(<fingerprint>)``.  Busy
time is the union of the operations' intervals, averaged over the
devices.  The breakdown attributes device time by executable, since the
program's eager stages run one small executable each.  A kernel's time is
the summed duration of the operations whose ``tf_op`` (the JAX name
stack the operation was traced under, e.g.
``jit(probe_bucketed_blocked)/while/body/closed_call/pallas_call``)
matches it, whether it runs alone or inside a larger jit.
``ProfileData`` does not expose that stat, so :func:`op_paths` reads it
from the event metadata of the raw protobuf.  Idle gaps are labelled by
the host event that covers most of the gap, so a gap says what the host
was doing.
"""
from __future__ import annotations

import re
from dataclasses import dataclass
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: ``jit_gather(1339...)`` -> ``jit_gather``
FINGERPRINT = re.compile(r"\(\d+\)$")


@dataclass
class Trace:
    devices: dict  # plane name -> [(name, start_ns, dur_ns)] device ops
    modules: dict  # plane name -> [(name, start_ns, dur_ns)] executable runs
    host: list  # [(name, start_ns, dur_ns)] host events, every thread
    paths: dict | None = None  # plane name -> {op name: {tf_op, ...}}


def find_xplane(log_dir: str | Path) -> Path:
    found = sorted(Path(log_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load(path: str | Path) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    devices, modules, host = {}, {}, []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            ops, mods = [], []
            for line in plane.lines:
                events = [(e.name, e.start_ns, e.duration_ns) for e in line.events]
                if line.name == OPS_LINE:
                    ops += events
                elif line.name == MODULES_LINE:
                    mods += events
            devices[plane.name] = ops
            modules[plane.name] = mods
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(e.name, e.start_ns, e.duration_ns) for e in line.events
                         if e.duration_ns > 0]
    return Trace(devices, modules, host, op_paths(path))


def _varint(b: bytes, i: int) -> tuple[int, int]:
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return x, i


def _fields(b: bytes):
    """``(field number, value)`` of one protobuf message, wire format."""
    i = 0
    while i < len(b):
        key, i = _varint(b, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(b, i)
        elif kind == 1:
            value, i = b[i:i + 8], i + 8
        elif kind == 5:
            value, i = b[i:i + 4], i + 4
        elif kind == 2:
            n, i = _varint(b, i)
            value, i = b[i:i + n], i + n
        else:
            raise ValueError(f"protobuf wire type {kind}")
        yield key >> 3, value


def op_paths(path: str | Path) -> dict:
    """``{plane: {op name: {tf_op, ...}}}`` of the device planes: the name
    stack of each operation, from the ``tf_op`` stat of its event metadata
    (XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4,
    .stat_metadata = 5; map entries key = 1, value = 2; XEventMetadata.name
    = 2, .stats = 5; XStatMetadata.name = 2; XStat.metadata_id = 1,
    .str_value = 5, .ref_value = 7)."""
    out = {}
    for field, plane in _fields(Path(path).read_bytes()):
        if field != 1:
            continue
        parts = list(_fields(plane))
        name = next((v.decode() for k, v in parts if k == 2), "")
        if not DEVICE_PLANE.match(name):
            continue
        stat_names = {}
        for k, v in parts:
            if k == 5:
                entry = dict(_fields(v))
                meta = dict(_fields(entry.get(2, b"")))
                stat_names[entry.get(1, 0)] = meta.get(2, b"").decode()
        tf_op = {i for i, n in stat_names.items() if n == "tf_op"}
        ops: dict = {}
        for k, v in parts:
            if k != 4:
                continue
            meta = list(_fields(dict(_fields(v)).get(2, b"")))
            op = next((x.decode(errors="replace") for f, x in meta if f == 2), "")
            for f, x in meta:
                if f != 5:
                    continue
                stat = dict(_fields(x))
                if stat.get(1) not in tf_op:
                    continue
                value = stat.get(5)
                if value is None and 7 in stat:
                    value = stat_names.get(stat[7], "").encode()
                if value:
                    ops.setdefault(op, set()).add(value.decode(errors="replace"))
        out[name] = ops
    return out


def union_ns(intervals) -> float:
    """Length of the union of ``(start, dur)`` intervals."""
    total, end = 0.0, float("-inf")
    for start, dur in sorted(intervals):
        stop = start + dur
        if stop <= end:
            continue
        total += stop - max(start, end)
        end = stop
    return total


def busy_s(trace: Trace) -> float:
    """Seconds in which some operation ran, averaged over the devices."""
    if not trace.devices:
        return 0.0
    per = [union_ns([(s, d) for _, s, d in ops]) for ops in trace.devices.values()]
    return sum(per) / len(per) / 1e9


def executable(name: str) -> str:
    """An executable run's name without its fingerprint."""
    return FINGERPRINT.sub("", name)


def kernel_s(trace: Trace, pattern: str) -> tuple[float, int]:
    """Summed seconds and count of the device operations whose ``tf_op``
    name stack matches ``pattern``, averaged over the devices."""
    rx = re.compile(pattern)
    if not trace.devices:
        return 0.0, 0
    total, count = 0.0, 0
    for plane, ops in trace.devices.items():
        paths = (trace.paths or {}).get(plane, {})
        hit = {op for op, stacks in paths.items() if any(rx.search(s) for s in stacks)}
        for name, _, dur in ops:
            if name in hit:
                total += dur
                count += 1
    n = len(trace.devices)
    return total / n / 1e9, count // n


def top_ops(trace: Trace, n: int = 10) -> list:
    """The ``n`` executables with the most device time, in seconds."""
    by_name: dict[str, float] = {}
    for mods in trace.modules.values():
        for name, _, dur in mods:
            key = executable(name)
            by_name[key] = by_name.get(key, 0.0) + dur
    k = max(len(trace.modules), 1)
    ranked = sorted(by_name.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / k / 1e9] for name, ns in ranked]


def gaps(ops) -> list[tuple[float, float]]:
    """Idle ``(start, end)`` intervals between the device's operations."""
    out, end = [], None
    for start, dur in sorted((s, d) for _, s, d in ops):
        if end is not None and start > end:
            out.append((end, start))
        end = start + dur if end is None else max(end, start + dur)
    return out


def idle_gaps(trace: Trace, n: int = 10) -> list:
    """The ``n`` longest idle gaps of the first device, each labelled by
    the host event that overlaps it most (the shortest on a tie)."""
    if not trace.devices:
        return []
    first = sorted(trace.devices)[0]
    longest = sorted(gaps(trace.devices[first]), key=lambda g: g[0] - g[1])[:n]
    out = []
    for lo, hi in longest:
        best, best_key = "none", None
        for name, start, dur in trace.host:
            overlap = min(hi, start + dur) - max(lo, start)
            if overlap <= 0:
                continue
            key = (-overlap, dur)
            if best_key is None or key < best_key:
                best, best_key = name, key
        out.append([best, (hi - lo) / 1e9])
    return out
