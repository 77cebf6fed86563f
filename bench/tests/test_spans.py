"""The span readers (``spans.py``): attribution to the innermost program
span, the device's idle split by what the host was in, launch
correlation, and the refusal to guess when launches and runs do not
pair.  Synthetic traces in ns; then the recorded v5e tick."""
from pathlib import Path
from types import SimpleNamespace

import pytest

import run
import spans
import trace_reduce as tr
from conftest import BENCH

DEV = "/device:TPU:0"
DATA = Path(__file__).resolve().parent / "data" / "tick_spans.xplane.pb"
IDLE = ("plan_idle_pct", "dispatch_idle_pct", "sync_idle_pct")


def _trace(host, runs, ops=None):
    """A trace of one device whose operations are its module runs."""
    mods = [(f"jit_{i}(1)", s, d) for i, (s, d) in enumerate(runs)]
    return tr.Trace(devices={DEV: ops if ops is not None else mods},
                    modules={DEV: mods}, host=host)


def _launch(t):
    return (spans.LAUNCH, t, 1)


#: one tick: plan 5-20, execute 20-95 with an MSJ attempt (probe 30-50, a
#: read 55-60) and an EVAL attempt (reduce 62-80), insert 95-98
HOST = [
    ("svc.tick", 0, 100), ("svc.plan", 5, 15), ("svc.execute", 20, 75),
    ("ft.attempt", 22, 38), ("msj.probe", 30, 20), ("host.sync", 55, 5),
    ("ft.attempt", 60, 30), ("eval.reduce", 62, 18), ("svc.insert", 95, 3),
    ("PjitFunction(add)", 30, 3),
    _launch(32), _launch(41), _launch(64), _launch(71),
]
RUNS = [(32, 6), (41, 4), (64, 5), (71, 9)]


def _ctx(trace, window_s=100e-9, ticks=1):
    return SimpleNamespace(trace=trace, window_s=window_s, ticks=[None] * ticks)


def _read(name, ctx):
    return run.named("metrics", name).read(ctx)


def test_innermost_is_the_latest_start():
    st = spans.Stacks(spans.program_spans(_trace(HOST, RUNS)))
    assert st.at(35) == ("svc.tick", "svc.execute", "ft.attempt", "msj.probe")
    assert st.at(57) == ("svc.tick", "svc.execute", "ft.attempt", "host.sync")
    assert st.at(10) == ("svc.tick", "svc.plan")
    assert st.at(-1) == () and st.at(100) == ()
    # overlapping, not nested: the later start is innermost
    cross = spans.Stacks([("svc.plan", 0, 10), ("svc.trim", 5, 10)])
    assert cross.at(7) == ("svc.plan", "svc.trim")
    assert cross.at(12) == ("svc.trim",)
    assert list(cross.split(-2, 20)) == [
        ((), 2), (("svc.plan",), 5), (("svc.plan", "svc.trim"), 5),
        (("svc.trim",), 5), ((), 5)]


def test_idle_split_by_host_activity():
    trace = _trace(HOST, RUNS)
    ctx = _ctx(trace)
    got = {name: _read(name, ctx) for name in IDLE}
    # idle 0-32 (tick 0-5, plan 5-20, execute 20-22, attempt 22-30,
    # probe 30-32), 38-41 probe, 45-64 (probe 45-50, attempt 50-55, sync
    # 55-60, execute 60, attempt 60-62, reduce 62-64), 69-71 reduce,
    # 80-100 (execute 80-95, insert 95-98, tick 98-100)
    assert got["plan_idle_pct"] == pytest.approx(5 + 15 + 3 + 2)
    assert got["sync_idle_pct"] == pytest.approx(5)
    assert got["dispatch_idle_pct"] == pytest.approx(
        2 + 8 + 2 + 3 + 5 + 5 + 2 + 2 + 2 + 15)
    busy = tr.busy_s(trace) * 1e9
    assert sum(got.values()) == pytest.approx(100 - busy)


def test_device_time_by_launching_phase():
    ctx = _ctx(_trace(HOST, RUNS), ticks=2)
    assert _read("msj_device_ms_per_tick", ctx) == pytest.approx((6 + 4) / 2 / 1e6)
    assert _read("eval_device_ms_per_tick", ctx) == pytest.approx((5 + 9) / 2 / 1e6)
    assert _read("host_syncs_per_tick", ctx) == 0.5


def test_device_clock_behind_the_host_is_shifted():
    """Every run reads 10 ns before its launch: the split shifts the
    device by the largest lead, which puts it back where it ran; a device
    clock that leads nothing is left alone."""
    early = [(s - 10, d) for s, d in RUNS]
    shifted = {name: _read(name, _ctx(_trace(HOST, early))) for name in IDLE}
    want = {name: _read(name, _ctx(_trace(HOST, RUNS))) for name in IDLE}
    assert shifted == pytest.approx(want)
    pairs = spans.launched_runs(_trace(HOST, early))[DEV]
    assert spans.skew_ns(pairs) == 10
    assert spans.skew_ns(spans.launched_runs(_trace(HOST, RUNS))[DEV]) == 0


def test_unpaired_launches_raise_naming_both_counts():
    trace = _trace(HOST, RUNS[:3])
    with pytest.raises(ValueError, match=r"3 executable runs .* but 4 host launches"):
        spans.launched_runs(trace)
    for name in ("msj_device_ms_per_tick",) + IDLE:
        with pytest.raises(ValueError, match="do not pair"):
            _read(name, _ctx(trace))


def test_without_program_spans_the_readers_stay_silent():
    """A trace of a program without spans (the parent of this reader):
    nothing to read, no guess and no raise, even unpaired."""
    bare = _trace([_launch(32), _launch(41)], RUNS[:1])
    names = IDLE + ("host_syncs_per_tick", "msj_device_ms_per_tick",
                    "eval_device_ms_per_tick")
    assert all(_read(name, _ctx(bare)) is None for name in names)
    assert all(_read(name, _ctx(None)) is None for name in names)


def test_recorded_probe_trace_pairs_launches_with_runs():
    """The v5e probe trace of a program without spans: its 396 launches
    pair with its 396 runs, and the span readers find nothing to read."""
    probe = tr.load(BENCH / "tests" / "data" / "probe_small.xplane.pb")
    pairs = spans.launched_runs(probe)[DEV]
    assert len(pairs) == 396
    assert all(t <= t_next for (t, *_), (t_next, *_) in zip(pairs, pairs[1:]))
    assert spans.program_spans(probe) == []
    assert _read("msj_device_ms_per_tick", _ctx(probe)) is None


# --------------------------------------------------------------------------
# The recorded tick: one warm bsgf-tenants.reload tick at 2^9 rows, TPU v5e
# --------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tick():
    return tr.load(DATA)


def test_recorded_tick_pairs_and_nests(tick):
    found = spans.program_spans(tick)
    names = [n for n, _, _ in found]
    assert names.count("svc.tick") == 1 and "svc.execute" in names
    assert {"msj.probe", "msj.publish", "eval.reduce", "eval.publish",
            "host.sync"} <= set(names)
    pairs = spans.launched_runs(tick)[DEV]
    assert len(pairs) == len(tick.modules[DEV]) > 100
    stacks = spans.Stacks(found)
    # every executable of the tick is launched inside the service's spans
    inside = [p for p in pairs if stacks.at(p[0])]
    assert len(inside) == len(pairs)


def test_recorded_tick_phases_cover_the_device(tick):
    ctx = _ctx(tick)
    busy_ms = tr.busy_s(tick) * 1e3
    msj = _read("msj_device_ms_per_tick", ctx)
    ev = _read("eval_device_ms_per_tick", ctx)
    assert msj > 0 and ev > 0
    assert msj + ev >= 0.9 * busy_ms
    assert _read("host_syncs_per_tick", ctx) > 10


def test_recorded_tick_idle_split_is_a_partition(tick):
    stacks = spans.Stacks(spans.program_spans(tick))
    idle = spans.idle_by_stack(tick, stacks, spans.launched_runs(tick))
    by_class = {}
    for stack, ns in idle.items():
        k = spans.idle_class(stack)
        by_class[k] = by_class.get(k, 0.0) + ns
    assert by_class["dispatch"] > 0 and by_class["sync"] > 0
    assert by_class["plan"] > 0
    ops = tick.devices[DEV]
    shift = spans.skew_ns(spans.launched_runs(tick)[DEV])
    lo = min(stacks.starts[0], min(s for _, s, _ in ops) + shift)
    hi = max(stacks.starts[-1], max(s + d for _, s, d in ops) + shift)
    assert sum(idle.values()) == pytest.approx(
        hi - lo - tr.busy_s(tick) * 1e9, rel=1e-9)


def test_tick_trace_is_small():
    assert DATA.stat().st_size < 1_000_000
    assert (BENCH / "tests" / "data" / "probe_small.xplane.pb").is_file()
