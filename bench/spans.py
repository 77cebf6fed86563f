"""The program's own spans in a profiler trace, on the device's clock
(PERF.md, Layers).

The service, the executor and every MSJ/EVAL phase run under
``jax.profiler.TraceAnnotation`` spans, which the trace holds as host
events (``Trace.host``) named as in :data:`SPANS`.  Two things are read
from them:

* **Attribution.**  A moment, or a launch, belongs to the innermost
  program span that contains it: the containing span that starts latest.
  Idle time of the device is split by the stack of spans over it
  (:func:`idle_by_stack`).
* **Launch correlation.**  The program's eager stages run one small
  executable each, so a ``tf_op`` name stack cannot say which phase ran
  an executable.  Each executable run is paired with its host launch
  instead: on each device, the host's ``PJRT_LoadedExecutable_Execute``
  events and the device's ``XLA Modules`` runs, both in time order, the
  k-th launch with the k-th run (:func:`launched_runs`).  When the counts
  differ the pairing would be a guess, so it raises.

The device's clock can read earlier than the host's: in the recorded
v5e traces every run starts about 1 ms before its launch.  No run starts
before its launch, so :func:`skew_ns` takes the largest such lead of a
pair and the idle split shifts the device's intervals by it.
"""
from __future__ import annotations

import bisect

#: the program's span names (PERF.md, Layers, span table)
SPANS = frozenset({
    "svc.submit", "svc.register", "svc.tick", "svc.fuse", "svc.lookup",
    "svc.plan", "svc.trim", "svc.verify", "svc.execute", "svc.insert",
    "svc.scatter", "ft.attempt", "msj.count", "msj.bloom", "msj.shuffle.fwd",
    "msj.xfer", "msj.probe", "msj.scatter", "msj.publish", "eval.shuffle",
    "eval.reduce", "eval.publish", "host.sync",
})
#: the host event of one executable launch (the C API call, main thread)
LAUNCH = "PJRT_LoadedExecutable_Execute"


def program_spans(trace) -> list:
    """``(name, start_ns, dur_ns)`` of the program's spans, by start and,
    at one start, the longer (outer) first."""
    return sorted((e for e in trace.host if e[0] in SPANS),
                  key=lambda e: (e[1], -e[2]))


class Stacks:
    """The stack of program spans over each moment: elementary intervals
    ``[starts[i], starts[i+1])`` each with the names of the spans over it,
    innermost (latest start) last."""

    def __init__(self, spans):
        edges = sorted({s for _, s, _ in spans} | {s + d for _, s, d in spans})
        self.starts = edges
        self.stacks = []
        order = sorted(spans, key=lambda e: (e[1], -e[2]))
        active: list = []
        j = 0
        for lo in edges:
            active = [e for e in active if e[1] + e[2] > lo]
            while j < len(order) and order[j][1] <= lo:
                if order[j][1] + order[j][2] > lo:
                    active.append(order[j])
                j += 1
            active.sort(key=lambda e: (e[1], -e[2]))
            self.stacks.append(tuple(e[0] for e in active))

    def at(self, t: float) -> tuple:
        """The stack over the moment ``t`` (empty outside every span)."""
        i = bisect.bisect_right(self.starts, t) - 1
        return self.stacks[i] if i >= 0 else ()

    def split(self, lo: float, hi: float):
        """``(stack, ns)`` pieces of the interval ``[lo, hi)``."""
        i = bisect.bisect_right(self.starts, lo)  # the first edge after lo
        t = lo
        while t < hi:
            end = min(hi, self.starts[i]) if i < len(self.starts) else hi
            yield self.at(t), end - t
            t = end
            i += 1


def launched_runs(trace) -> dict:
    """``{device: [(launch_start_ns, run_name, run_start_ns, run_dur_ns)]}``:
    each executable run paired with its host launch in launch order.
    Raises when a device's run count differs from the launch count."""
    launches = sorted(s for name, s, _ in trace.host if name == LAUNCH)
    out = {}
    for plane, mods in trace.modules.items():
        runs = sorted(mods, key=lambda e: e[1])
        if len(runs) != len(launches):
            raise ValueError(
                f"{plane}: {len(runs)} executable runs ('XLA Modules') but "
                f"{len(launches)} host launches ({LAUNCH!r}); they do not pair")
        out[plane] = [(t, n, s, d) for t, (n, s, d) in zip(launches, runs)]
    return out


def skew_ns(pairs) -> float:
    """How far the device's clock reads behind the host's: the largest
    lead of a run's start over its launch's (0 when none leads)."""
    return max([t - s for t, _, s, _ in pairs] + [0.0])


def idle_by_stack(trace, stacks: Stacks, pairs: dict) -> dict:
    """Idle nanoseconds of the device by the stack of program spans over
    them, averaged over the devices.  Idle is the complement of the
    device's operations, clock-shifted by :func:`skew_ns`, within the
    extent of the program's spans and the operations together."""
    out: dict = {}
    n = 0
    for plane, ops in trace.devices.items():
        shift = skew_ns(pairs.get(plane, ()))
        iv = sorted((s + shift, s + shift + d) for _, s, d in ops)
        if not iv or not stacks.starts:
            continue
        n += 1
        t = min(iv[0][0], stacks.starts[0])
        end = max(max(b for _, b in iv), stacks.starts[-1])
        gaps = []
        for a, b in iv:
            if a > t:
                gaps.append((t, a))
            t = max(t, b)
        if end > t:
            gaps.append((t, end))
        for lo, hi in gaps:
            for stack, ns in stacks.split(lo, hi):
                out[stack] = out.get(stack, 0.0) + ns
    return {k: v / n for k, v in out.items()} if n else {}


def idle_class(stack: tuple) -> str:
    """Whose idle time it is: ``sync`` (innermost span a ``host.sync``),
    ``dispatch`` (inside ``svc.execute``), ``plan`` (innermost another
    ``svc.*`` span) or ``none``."""
    if not stack:
        return "none"
    if stack[-1] == "host.sync":
        return "sync"
    if "svc.execute" in stack:
        return "dispatch"
    if stack[-1].startswith("svc."):
        return "plan"
    return "none"


def idle_pct(ctx, kind: str):
    """Share of the window, in %, in which the device is idle and the host
    is in spans of ``kind`` (:func:`idle_class`); None without program
    spans in the trace."""
    if ctx.trace is None or not ctx.trace.devices or ctx.window_s <= 0:
        return None
    spans = program_spans(ctx.trace)
    if not spans:
        return None
    stacks = Stacks(spans)
    pairs = launched_runs(ctx.trace)
    idle = idle_by_stack(ctx.trace, stacks, pairs)
    ns = sum(v for k, v in idle.items() if idle_class(k) == kind)
    return 100.0 * ns / (ctx.window_s * 1e9)


def device_ms_per_tick(ctx, prefix: str):
    """Device time, ms per tick, of the executable runs launched under a
    span named ``<prefix>.*``, averaged over the devices; None without
    program spans in the trace."""
    if ctx.trace is None or not ctx.trace.devices or not ctx.ticks:
        return None
    spans = program_spans(ctx.trace)
    if not spans:
        return None
    stacks = Stacks(spans)
    pairs = launched_runs(ctx.trace)
    if not pairs:
        return None
    total = 0.0
    for runs in pairs.values():
        total += sum(d for t, _, _, d in runs
                     if any(s.startswith(prefix + ".") for s in stacks.at(t)))
    return total / len(pairs) / 1e6 / len(ctx.ticks)
