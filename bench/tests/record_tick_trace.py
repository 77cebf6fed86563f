"""Record ``data/tick_spans.xplane.pb``, the small TPU trace that
``test_spans.py`` reads: one warm ``bsgf-tenants.reload`` tick at 2^9 rows
per relation, its relations reloaded first, under the JAX profiler.

    python bench/tests/record_tick_trace.py [--out PATH] [--seed N]

Needs a TPU: the trace is of the chip's device plane and the service's
spans on its host clock.  A tick launches about 10^4 executables, so the
raw trace is ~15 MB; :func:`shrink` keeps what the span readers read —
the device's ``XLA Modules`` and ``XLA Ops`` events (times, and names
cut before an operation's HLO text, without stats) and, on the host, the
program's spans and the executable launches — and drops every other
plane, line and event.
Prints the counts the tests pin.
"""
from __future__ import annotations

import argparse
import os
import shutil
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
sys.path.insert(0, str(BENCH))

LOG2_ROWS = 9
#: device lines the readers read; host events are kept by name
DEVICE_LINES = {"XLA Modules", "XLA Ops"}


def _varint(x: int) -> bytes:
    out = bytearray()
    x &= (1 << 64) - 1
    while True:
        b = x & 0x7F
        x >>= 7
        if x:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def _field(num: int, value) -> bytes:
    """One protobuf field: bytes length-delimited, ints as varints."""
    if isinstance(value, (bytes, bytearray)):
        return _varint(num << 3 | 2) + _varint(len(value)) + bytes(value)
    return _varint(num << 3) + _varint(value)


def _message(fields) -> bytes:
    return b"".join(_field(k, v) for k, v in fields)


def _shrink_plane(plane: bytes, keep_host) -> bytes | None:
    """A plane with only the lines and events the readers read, and only
    the event metadata those events name (XPlane: name = 2, lines = 3,
    event_metadata = 4; XLine: name = 2, events = 4; XEvent: metadata_id
    = 1, stats = 4; XEventMetadata: name = 2, metadata = 3, stats = 5)."""
    from trace_reduce import DEVICE_PLANE, _fields

    parts = list(_fields(plane))
    name = next((v.decode() for k, v in parts if k == 2), "")
    device = bool(DEVICE_PLANE.match(name))
    if not device and name != "/host:CPU":
        return None
    names = {}
    for k, v in parts:
        if k == 4:
            entry = dict(_fields(v))
            meta = dict(_fields(entry.get(2, b"")))
            names[entry.get(1, 0)] = meta.get(2, b"").decode(errors="replace")
    used, out = set(), []
    for k, v in parts:
        if k != 3:
            continue
        line = list(_fields(v))
        lname = next((x.decode() for f, x in line if f == 2), "")
        if device and lname not in DEVICE_LINES:
            continue
        events = []
        for f, x in line:
            if f != 4:
                continue
            ev = list(_fields(x))
            mid = next((y for g, y in ev if g == 1), 0)
            if device:
                ev = [(g, y) for g, y in ev if g != 4]
            elif not keep_host(names.get(mid, "")):
                continue
            used.add(mid)
            events.append(_message(ev))
        if events:
            out.append((3, _message([(f, x) for f, x in line if f != 4]
                                    + [(4, e) for e in events])))
    if not out:
        return None
    head = [(k, v) for k, v in parts if k in (1, 2, 5)]
    meta = []
    for k, v in parts:
        if k != 4:
            continue
        entry = dict(_fields(v))
        if entry.get(1, 0) not in used:
            continue
        m = [(g, y) for g, y in _fields(entry.get(2, b"")) if g not in (3, 5)]
        if device:  # an operation's HLO text after its name: unread
            m = [(g, y.split(b" = ")[0] if g == 2 else y) for g, y in m]
        meta.append((4, _message([(1, entry.get(1, 0)), (2, _message(m))])))
    return _message(head + out + meta)


def shrink(src, dst) -> None:
    """Write to ``dst`` the part of the trace ``src`` that the span
    readers read (XSpace: planes = 1)."""
    import spans
    from trace_reduce import _fields

    def keep_host(name: str) -> bool:
        return name in spans.SPANS or name == spans.LAUNCH

    planes = []
    for k, v in _fields(Path(src).read_bytes()):
        if k == 1:
            small = _shrink_plane(v, keep_host)
            if small is not None:
                planes.append((1, small))
    Path(dst).write_bytes(_message(planes))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default=str(HERE / "data" / "tick_spans.xplane.pb"))
    ap.add_argument("--seed", type=int, default=2147483901)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    if jax.devices()[0].platform != "tpu":
        print("record_tick_trace: needs a TPU", file=sys.stderr)
        return 3
    import numpy as np

    import run
    import sut
    import trace_reduce
    from datagen import make_versions

    spec = run.load_spec()
    _, config, traffic = run.load_cell(spec, "bsgf-tenants.reload")
    config = {**config, "rows_per_relation": 2 ** LOG2_ROWS}
    sut.enable_compile_cache()
    versions = make_versions(config, traffic["versions"], args.seed)
    server = sut.ProgramServer(config, versions)
    driver = run.named("drivers", traffic["driver"]).Driver(
        server, traffic, versions[0].keys())
    driver.warm()
    log_dir = tempfile.mkdtemp(prefix="tick_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    tick = driver.tick()
    jax.profiler.stop_trace()
    shrink(trace_reduce.find_xplane(log_dir), args.out)
    shutil.rmtree(log_dir, ignore_errors=True)
    trace = trace_reduce.load(args.out)
    import spans

    pairs = spans.launched_runs(trace)
    found = spans.program_spans(trace)
    names = sorted({n for n, _, _ in found})
    print(f"{args.out}: {Path(args.out).stat().st_size} B, {tick.n_jobs} jobs, "
          f"ok {all(tick.ok)}, runs {[len(v) for v in pairs.values()]}, "
          f"spans {len(found)} of {names}, busy {trace_reduce.busy_s(trace)} s, "
          f"latency {np.median(tick.latencies)} s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
