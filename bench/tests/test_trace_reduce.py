"""The trace reduction on a small recorded TPU v5e trace: the bucketed
probe kernel vmapped over 8 shards and called eagerly three times, as the
MSJ probe stage calls it, each followed by an eager reduction."""
from pathlib import Path

import pytest

import probe_events
import trace_reduce as tr

DATA = Path(__file__).resolve().parent / "data" / "probe_small.xplane.pb"


@pytest.fixture(scope="module")
def trace():
    return tr.load(DATA)


def test_union_of_intervals():
    assert tr.union_ns([(0, 10), (5, 10), (20, 5)]) == 20
    assert tr.union_ns([(0, 10), (2, 3)]) == 10
    assert tr.union_ns([]) == 0
    ops = [("a", 0, 10), ("b", 5, 10), ("c", 20, 5)]
    assert tr.gaps(ops) == [(15, 20)]


def test_one_tpu_plane(trace):
    assert list(trace.devices) == ["/device:TPU:0"]
    assert trace.devices["/device:TPU:0"] and trace.modules["/device:TPU:0"]


def test_busy_and_idle(trace):
    busy = tr.busy_s(trace)
    ops = trace.devices["/device:TPU:0"]
    span = (max(s + d for _, s, d in ops) - min(s for _, s, _ in ops)) / 1e9
    assert 0 < busy < span
    gaps = tr.idle_gaps(trace)
    assert gaps and all(isinstance(name, str) and s > 0 for name, s in gaps)
    assert sum(s for _, s in tr.idle_gaps(trace, n=10**6)) == pytest.approx(
        span - busy, rel=1e-6)


def test_probe_kernel_events(trace):
    """Three eager calls of the kernel vmapped over 8 shards: 24 kernel
    operations, inside the three ``jit_probe_bucketed_blocked`` runs."""
    seconds, count = tr.kernel_s(trace, probe_events.PATTERN)
    assert count == 24
    runs = [d for n, _, d in trace.modules["/device:TPU:0"]
            if tr.executable(n) == "jit_probe_bucketed_blocked"]
    assert len(runs) == 3
    assert 0 < seconds < sum(runs) / 1e9
    names = [name for name, _ in tr.top_ops(trace)]
    assert "jit_probe_bucketed_blocked" in names


def _ctx(trace, backends):
    from types import SimpleNamespace

    jobs = [{"backend": b, "recv_fwd": 10, "hits": 1, "key_width": 1} for b in backends]
    return SimpleNamespace(trace=trace, ticks=[SimpleNamespace(jobs=jobs)])


def test_probe_inside_a_larger_jit_is_matched():
    """The kernel's operation inside an enclosing jit: matched by its name
    stack, not by an executable's name."""
    op = "%closed_call.3 = s32[8,512,128]{2,1,0} fusion(...), kind=kCustom"
    stack = "jit(step)/jit(main)/vmap(jit(probe_bucketed_blocked))/while/body/closed_call/pallas_call"
    trace = tr.Trace(
        devices={"/device:TPU:0": [(op, 0, 500), ("%other = ...", 600, 100)]},
        modules={"/device:TPU:0": [("jit_step(1)", 0, 800)]},
        host=[], paths={"/device:TPU:0": {op: {stack}, "%other = ...": {"jit(step)/add"}}},
    )
    assert tr.kernel_s(trace, probe_events.PATTERN) == (500e-9, 1)
    assert probe_events.seconds(_ctx(trace, ["pallas"])) == 500e-9


def test_probe_reader_raises_when_the_kernel_is_out_of_sight(trace):
    blind = tr.Trace(trace.devices, trace.modules, trace.host, paths={})
    with pytest.raises(RuntimeError, match="Pallas MSJ jobs"):
        probe_events.seconds(_ctx(blind, ["pallas", "sorted"]))
    assert probe_events.seconds(_ctx(blind, ["sorted"])) is None
