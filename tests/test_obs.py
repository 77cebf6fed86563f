"""Tracing + metrics layer (DESIGN.md §14): tracer/span units, the metric
registry (counters, histograms, counter_attr compatibility properties,
JSONL sink), the traced-executor integration (spans on records, tracer=None
bit-identity), the no-double-count replay regression on reports with
cancelled/tainted records, and the bench regression gate."""
from __future__ import annotations

import copy
import io
import json
import math
from pathlib import Path

import numpy as np
import pytest

from benchmarks import regression
from repro.core import queries as Q
from repro.core.algebra import Atom, BSGF, all_of
from repro.core.costmodel import stats_of_db
from repro.core.executor import Executor, ExecutorConfig, JobRecord, Report
from repro.core.planner import plan_greedy
from repro.core.relation import db_from_dict
from repro.engine.comm import SimComm
from repro.obs import MetricRegistry, Span, Tracer, trace_events
from repro.obs.metrics import Counter, Histogram, JsonlSink, counter_attr
from repro.obs.tracer import rebase, scale_spans

REPO = Path(__file__).resolve().parents[1]


# --------------------------------------------------------------------------
# Tracer / spans
# --------------------------------------------------------------------------


class TestTracer:
    def test_nested_spans(self):
        tr = Tracer()
        with tr.capture() as root:
            with tr.span("outer", rows=3) as out:
                with tr.span("inner"):
                    pass
            with tr.span("sibling"):
                pass
        assert [sp.name for sp in root] == ["outer", "sibling"]
        assert [sp.name for sp in root[0].children] == ["inner"]
        assert out.args == {"rows": 3}
        assert all(sp.dur >= 0.0 for r in root for sp in r.walk())

    def test_capture_isolates_attempts(self):
        tr = Tracer()
        with tr.capture() as a:
            with tr.span("first"):
                pass
        with tr.capture() as b:
            with tr.span("second"):
                pass
        assert [sp.name for sp in a] == ["first"]
        assert [sp.name for sp in b] == ["second"]

    def test_span_outside_capture_tolerated(self):
        tr = Tracer()
        with tr.span("orphan"):
            pass  # must not raise

    def test_post_hoc_arg_attachment(self):
        tr = Tracer()
        with tr.capture() as root:
            with tr.span("io") as sp:
                pass
            sp.args["bytes"] = 4096
        assert root[0].args["bytes"] == 4096

    def test_rebase_and_scale(self):
        spans = [Span("a", t0=10.0, dur=2.0,
                      children=[Span("b", t0=10.5, dur=1.0)])]
        rebase(spans, 10.0, 2.0)
        assert spans[0].t0 == 0.0 and spans[0].dur == 4.0
        # children share the parent's origin: offsets stay job-relative
        assert spans[0].children[0].t0 == 1.0
        assert spans[0].children[0].dur == 2.0
        scale_spans(spans, 0.5)
        assert spans[0].dur == 2.0 and spans[0].children[0].t0 == 0.5

    def test_walk_covers_tree(self):
        sp = Span("a", children=[Span("b", children=[Span("c")]), Span("d")])
        assert [s.name for s in sp.walk()] == ["a", "b", "c", "d"]


# --------------------------------------------------------------------------
# Metric registry
# --------------------------------------------------------------------------


class TestMetrics:
    def test_counter_and_gauge(self):
        m = MetricRegistry()
        m.counter("msj.jobs").inc()
        m.counter("msj.jobs").add(4)
        m.gauge("svc.queue.depth").set(7)
        assert m.counter("msj.jobs").value == 5
        assert m.gauge("svc.queue.depth").value == 7
        assert "msj.jobs" in m and "nope" not in m

    def test_type_conflict_raises(self):
        m = MetricRegistry()
        m.counter("x")
        with pytest.raises(TypeError):
            m.histogram("x")

    def test_histogram_percentiles_bounded_error(self):
        h = Histogram("lat")
        vals = [0.001 * (i + 1) for i in range(1000)]
        for v in vals:
            h.observe(v)
        assert h.count == 1000 and h.min == vals[0] and h.max == vals[-1]
        for p in (0.5, 0.95, 0.99):
            exact = vals[int(p * len(vals)) - 1]
            got = h.percentile(p)
            # HDR convention: upper bucket edge — never below the exact
            # quantile's bucket, within one sub-bucket (~3%) above it
            assert exact * (1 - 2**-h.sub_bits) <= got <= exact * (1 + 2**-4)
        assert h.percentile(1.0) == vals[-1]

    def test_histogram_zero_and_empty(self):
        h = Histogram("z")
        assert h.percentile(0.5) == 0.0
        h.observe(0.0)
        assert h.percentile(0.5) == 0.0 and h.count == 1
        assert h.snapshot()["min"] == 0.0

    def test_counter_attr_property(self):
        class Thing:
            hits = counter_attr("t.hit")

            def __init__(self, metrics=None):
                self.metrics = metrics or MetricRegistry()

        t = Thing()
        t.hits += 1
        t.hits += 1
        assert t.hits == 2
        assert t.metrics.counter("t.hit").value == 2
        t.hits = 0  # assignment translates to a delta
        assert t.metrics.counter("t.hit").value == 0
        # two objects sharing one registry share the counter
        shared = MetricRegistry()
        a, b = Thing(shared), Thing(shared)
        a.hits += 3
        assert b.hits == 3

    def test_jsonl_sink_roundtrip(self):
        buf = io.StringIO()
        m = MetricRegistry()
        m.counter("c").add(2)
        m.histogram("h").observe(0.12345678901234567)
        with JsonlSink(buf) as sink:
            sink.write({"tick": 1}, extra="x")
            sink.write_registry(m, tick=2)
        lines = [json.loads(ln) for ln in buf.getvalue().splitlines()]
        assert lines[0] == {"tick": 1, "extra": "x"}
        assert lines[1]["metrics"]["c"] == 2
        # shortest-roundtrip float reprs: values come back bit-exact
        assert lines[1]["metrics"]["h"]["sum"] == 0.12345678901234567


# --------------------------------------------------------------------------
# Traced executor integration
# --------------------------------------------------------------------------


def _tiny_setup():
    q = BSGF("Z", ("x", "y"), Atom("R", "x", "y"),
             all_of(Atom("S", "x"), Atom("T", "y")))
    db_np = Q.gen_db([q], n_guard=96, n_cond=64)
    db = db_from_dict(db_np, P=2)
    plan = plan_greedy([q], stats_of_db(db))
    return db, plan


class TestTracedExecutor:
    def test_spans_recorded_and_untraced_identical(self):
        db, plan = _tiny_setup()
        env0, rep0 = Executor(dict(db), SimComm(2)).execute(plan)
        tr = Tracer()
        m = MetricRegistry()
        env1, rep1 = Executor(dict(db), SimComm(2), tracer=tr,
                              metrics=m).execute(plan)
        assert env0["Z"].to_set() == env1["Z"].to_set()
        assert all(r.spans == [] for r in rep0.records)
        for r in rep1.records:
            assert r.spans, "traced records must carry phase spans"
            names = [sp.name for sp in r.spans[0].walk()]
            assert names[0] == "ft.attempt"
            assert "msj.probe" in names or "eval.reduce" in names
            # spans nest inside the job slice after rebase/scale
            for sp in r.spans[0].walk():
                assert sp.t0 >= -1e-9
        # executor published report-derived metrics into the registry
        assert m.counter("msj.jobs").value == rep1.n_jobs
        assert m.counter("msj.shuffle.bytes").value == rep1.bytes_shuffled()
        # shuffled bytes and hits reach the stage spans from the job's
        # host-read stats, after the job: the stages read nothing
        for r in rep1.records:
            args = {sp.name: sp.args for sp in r.spans[-1].walk()}
            if "msj.probe" in args:
                assert args["msj.shuffle.fwd"]["bytes"] == r.stats["bytes_fwd"]
                assert args["msj.scatter"]["bytes"] == r.stats["bytes_bwd"]
                assert args["msj.probe"]["hits"] == r.stats["hits"]
            else:
                assert args["eval.shuffle"]["bytes"] == r.stats["bytes_fwd"]

    def test_disabled_tracer_records_nothing(self):
        db, plan = _tiny_setup()
        tr = Tracer(enabled=False)
        _, rep = Executor(dict(db), SimComm(2), tracer=tr).execute(plan)
        assert all(r.spans == [] for r in rep.records)


# --------------------------------------------------------------------------
# Spans on the profiler's clock (always on, tracer or not)
# --------------------------------------------------------------------------

#: span -> the span it must sit directly under (DESIGN.md §14)
PARENT = {
    "svc.fuse": "svc.tick", "svc.lookup": "svc.tick", "svc.plan": "svc.tick",
    "svc.trim": "svc.tick", "svc.verify": "svc.tick", "svc.execute": "svc.tick",
    "svc.insert": "svc.tick", "svc.scatter": "svc.tick",
    "ft.attempt": "svc.execute", "msj.publish": "svc.execute",
    "eval.publish": "svc.execute", "msj.count": "ft.attempt",
    "msj.shuffle.fwd": "ft.attempt", "msj.probe": "ft.attempt",
    "msj.scatter": "ft.attempt", "eval.shuffle": "ft.attempt",
    "eval.reduce": "ft.attempt",
}
PROGRAM = ("svc.", "msj.", "eval.", "ft.", "host.")


def _profiled(log_dir, fn):
    """Run ``fn`` under a profiler session; returns its result and the
    host events ``(name, start_ns, dur_ns, stats)`` of the program's
    spans, outer first at one start."""
    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(log_dir))
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path = sorted(Path(log_dir).rglob("*.xplane.pb"))[-1]
    events = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PROGRAM):
                    events.append((e.name, e.start_ns, e.duration_ns,
                                   dict(e.stats)))
    return out, sorted(events, key=lambda e: (e[1], -e[2]))


def _parents(events) -> list:
    """The enclosing span's name (or None) of each event, by nesting on
    the one host thread the service runs on."""
    out, stack = [], []
    for name, start, dur, _ in events:
        while stack and stack[-1][1] <= start:
            stack.pop()
        out.append(stack[-1][0] if stack else None)
        stack.append((name, start + dur))
    return out


def _service(tracer=None):
    from repro.service import SGFService, catalog_from_numpy

    q = BSGF("Z", ("x", "y"), Atom("R", "x", "y"),
             all_of(Atom("S", "x"), Atom("T", "y")))
    db_np = Q.gen_db([q], n_guard=96, n_cond=64)
    svc = SGFService(catalog_from_numpy(db_np, P=2), tracer=tracer)
    return svc, q, db_np


def _cold_tick(svc, q, db_np):
    """Reload S (a cold tick: every job runs) and serve one request."""
    svc.catalog.register("S", db_np["S"])
    req = svc.submit([q])
    svc.tick()
    return req


class TestProfilerSpans:
    def test_tick_spans_nest_on_the_profiler_clock(self, tmp_path, monkeypatch):
        import repro.obs.tracer as tracer_mod

        svc, q, db_np = _service()
        svc.submit([q])
        svc.tick()  # compile outside the session
        syncs = {"n": 0}
        real = tracer_mod.annotate

        def counting(name, **args):
            syncs["n"] += name == "host.sync"
            return real(name, **args)

        # host_read is the only caller of annotate through the module
        # global that names host.sync
        monkeypatch.setattr(tracer_mod, "annotate", counting)
        _, events = _profiled(tmp_path, lambda: _cold_tick(svc, q, db_np))
        names = [e[0] for e in events]
        ticks = [e for e in events if e[0] == "svc.tick"]
        assert [e[3]["tick"] for e in ticks] == [2]
        for name in ("svc.register", "svc.submit"):
            assert names.count(name) == 1
        parents = _parents(events)
        for (name, *_), parent in zip(events, parents):
            if name in PARENT:
                assert parent == PARENT[name], (name, parent)
            elif name in ("svc.register", "svc.submit", "svc.tick"):
                assert parent is None, (name, parent)
        for name in PARENT:
            if name != "msj.count":
                assert name in names, name
        plan = next(e for e in events if e[0] == "svc.plan")
        assert plan[3]["hit"] == 0
        assert next(e for e in events if e[0] == "svc.register")[3]["rel"] == "S"
        # one host.sync span per device read through the helper, each
        # inside the tick
        assert 0 < names.count("host.sync") == syncs["n"]
        tick_end = ticks[0][1] + ticks[0][2]
        assert all(ticks[0][1] <= s and s + d <= tick_end
                   for n, s, d, _ in events if n == "host.sync")

    def test_host_read_one_sync_per_device_value(self, tmp_path):
        import jax.numpy as jnp

        from repro.obs import host_read

        x = jnp.arange(6, dtype=jnp.int32)

        def reads():
            return [host_read(x.sum(), "a"), host_read(x, "b"),
                    host_read(x * 2, "c"), host_read(7, "on the host")]

        got, events = _profiled(tmp_path, reads)
        assert [int(got[0]), got[1].tolist(), int(got[3])] == [15, list(range(6)), 7]
        assert [(e[0], e[3]["site"]) for e in events] == [
            ("host.sync", "a"), ("host.sync", "b"), ("host.sync", "c")]

    def test_no_span_objects_without_tracer_and_bits_identical(
        self, tmp_path, monkeypatch
    ):
        made = {"n": 0}
        real_init = Span.__init__

        def counting(self, *a, **k):
            made["n"] += 1
            real_init(self, *a, **k)

        runs = {}
        svc, q, db_np = _service()
        svc.submit([q])
        svc.tick()
        monkeypatch.setattr(Span, "__init__", counting)
        runs["plain"] = _cold_tick(svc, q, db_np)
        runs["profiled"], _ = _profiled(tmp_path, lambda: _cold_tick(svc, q, db_np))
        assert made["n"] == 0, "tracer=None must create no Span"
        tsvc, _, _ = _service(Tracer())
        runs["traced"] = _cold_tick(tsvc, q, db_np)
        assert made["n"] > 0
        want = runs["plain"].outputs["Z"]
        for key in ("profiled", "traced"):
            got = runs[key].outputs["Z"]
            assert np.array_equal(np.asarray(got.data), np.asarray(want.data)), key
            assert np.array_equal(np.asarray(got.valid), np.asarray(want.valid)), key


# --------------------------------------------------------------------------
# No-double-count replay regression (cancelled + tainted records)
# --------------------------------------------------------------------------


def _chaos_report() -> Report:
    """Hand-built timeline with a speculation pair (winner + truncated
    cancelled loser) and a zero-wall tainted record — the shapes that
    historically double- or under-counted."""
    recs = [
        JobRecord(None, 0, 1.0, {}, start=0.0, end=1.0, slot=0),
        JobRecord(None, 0, 5.0, {}, start=0.0, end=5.0, slot=1,
                  outcome="failed"),
        # clone dispatched at 1.0 on slot 0, wins at 3.5
        JobRecord(None, 1, 2.5, {}, start=1.0, end=3.5, slot=0,
                  attempt=1, speculative=True),
        # original loser: wall truncated at the winner's end
        JobRecord(None, 1, 1.5, {}, start=2.0, end=3.5, slot=1,
                  attempt=0, cancelled=True, outcome="cancelled"),
        JobRecord(None, 2, 0.0, {}, start=5.0, end=5.0, slot=-1,
                  outcome="tainted"),
    ]
    return Report(recs)


class TestReplayNoDoubleCount:
    def test_slot_track_walls_sum_to_total_time(self):
        rep = _chaos_report()
        events = trace_events(rep)
        job_evs = [e for e in events
                   if e.get("ph") == "X" and e.get("cat") == "job"]
        assert len(job_evs) == len(rep.records)
        # exported walls, re-summed in the same round-major stable order
        # Report.total_time uses, must thread identical float additions
        walls = [e["args"]["wall"]
                 for e in sorted(job_evs, key=lambda e: e["args"]["round"])]
        assert sum(walls) == rep.total_time
        assert rep.net_time_by_events(1) == rep.total_time
        assert rep.net_time_by_events(None) == rep.net_time

    def test_replay_from_export_bit_exact(self):
        from repro.obs import report_from_trace

        rep = _chaos_report()
        doc = json.loads(json.dumps({"traceEvents": trace_events(rep)}))
        rep2 = report_from_trace(doc)
        assert rep2.total_time == rep.total_time
        assert rep2.net_time == rep.net_time
        for W in (None, 1, 2, 3):
            assert rep2.net_time_by_events(W) == rep.net_time_by_events(W)


# --------------------------------------------------------------------------
# Bench regression gate
# --------------------------------------------------------------------------

_MSJ = {
    "n_guard": 2048,
    "msj_roofline": [
        {"variant": "seed", "bytes_shuffled": 1000, "input_rows": 50,
         "jobs": 5, "net_s": 0.5, "total_s": 1.0, "forward_cap": 256},
    ],
    "probe_kernel": [{"backend": "sorted", "n": 1024, "kw": 2, "ms": 10.0}],
}

_SERVE = {
    "n_guard": 512,
    "service_throughput": [
        {"tenants": 2, "per_tenant": 1, "mode": "batched", "jobs": 4,
         "msj_jobs": 2, "bytes_shuffled": 100, "warm_queries": 0,
         "deduped": 0, "net_s": 1.0, "total_s": 1.0},
    ],
    "repeat_traffic": [
        {"mode": "repeat_cached", "jobs": 8, "bytes_shuffled": 200,
         "warm_queries": 5, "cold_queries": 3, "x_hits": 1, "plan_hits": 2,
         "net_s": 2.0, "total_s": 2.0},
    ],
    "acceptance": {
        "event_accounting_exact": True,
        "straggler": {"bit_identical": True, "speedup": 1.4},
    },
}


class TestRegressionGate:
    def test_self_compare_passes(self):
        assert regression.gate(copy.deepcopy(_MSJ), _MSJ) == []
        assert regression.gate(copy.deepcopy(_SERVE), _SERVE) == []

    def test_committed_baselines_self_compare(self):
        for name in ("BENCH_msj.json", "BENCH_serve.json"):
            base = regression.load(str(REPO / name))
            assert regression.gate(copy.deepcopy(base), base) == [], name

    def test_injected_timing_regression_fails(self):
        bad = copy.deepcopy(_MSJ)
        bad["msj_roofline"][0]["net_s"] *= 10
        probs = regression.gate(bad, _MSJ)
        assert len(probs) == 1 and "net_s regressed" in probs[0]
        # within tolerance: no failure
        ok = copy.deepcopy(_MSJ)
        ok["msj_roofline"][0]["net_s"] *= 1 + regression.TIME_TOL / 2
        assert regression.gate(ok, _MSJ) == []

    def test_kernel_rows_get_wide_band(self):
        # ms-scale micro-bench rows jitter 2x+; only order-of-magnitude
        # drift fails them
        noisy = copy.deepcopy(_MSJ)
        noisy["probe_kernel"][0]["ms"] *= 2.5
        assert regression.gate(noisy, _MSJ) == []
        bad = copy.deepcopy(_MSJ)
        bad["probe_kernel"][0]["ms"] *= 10
        probs = regression.gate(bad, _MSJ)
        assert len(probs) == 1 and "ms regressed" in probs[0]

    def test_deterministic_drift_fails_exactly(self):
        bad = copy.deepcopy(_SERVE)
        bad["service_throughput"][0]["bytes_shuffled"] += 1
        bad["repeat_traffic"][0]["warm_queries"] -= 1
        probs = regression.gate(bad, _SERVE)
        assert len(probs) == 2
        assert all("exact match required" in p for p in probs)

    def test_acceptance_flag_and_speedup_loss_fail(self):
        bad = copy.deepcopy(_SERVE)
        bad["acceptance"]["straggler"]["bit_identical"] = False
        bad["acceptance"]["straggler"]["speedup"] = 0.8
        probs = regression.gate(bad, _SERVE)
        assert any("acceptance flag lost" in p for p in probs)
        assert any("speedup lost" in p for p in probs)

    def test_absent_acceptance_key_is_hard_failure(self):
        # a dropped/renamed key must fail the gate, not vacuously pass —
        # regardless of the baseline value's type (bool, number, dict)
        for key in ("event_accounting_exact", "straggler"):
            bad = copy.deepcopy(_SERVE)
            del bad["acceptance"][key]
            probs = regression.gate(bad, _SERVE)
            assert any(
                f"acceptance.{key}: missing from current run" in p
                for p in probs
            ), (key, probs)
        bad = copy.deepcopy(_SERVE)
        del bad["acceptance"]["straggler"]["speedup"]  # non-bool leaf
        probs = regression.gate(bad, _SERVE)
        assert any("speedup: missing from current run" in p for p in probs)

    def test_missing_row_and_incomparable_sizes(self):
        cur = copy.deepcopy(_MSJ)
        cur["msj_roofline"] = []
        assert any("missing" in p for p in regression.gate(cur, _MSJ))
        cur = copy.deepcopy(_MSJ)
        cur["n_guard"] = 4096
        assert "incomparable" in regression.gate(cur, _MSJ)[0]

    def test_cli_exit_codes(self, tmp_path, capsys):
        base = tmp_path / "base.json"
        base.write_text(json.dumps(_MSJ))
        cur = tmp_path / "cur.json"
        bad = copy.deepcopy(_MSJ)
        bad["msj_roofline"][0]["total_s"] *= 100
        cur.write_text(json.dumps(bad))
        with pytest.raises(SystemExit) as e:
            regression.main(["--baseline", str(base), "--current", str(base)])
        assert e.value.code == 0
        with pytest.raises(SystemExit) as e:
            regression.main(["--baseline", str(base), "--current", str(cur)])
        assert e.value.code == 1


# --------------------------------------------------------------------------
# Service-layer metric plumbing (compat shim)
# --------------------------------------------------------------------------


class TestServiceMetricPlumbing:
    def test_shared_registry_single_namespace(self):
        from repro.service import SGFService, catalog_from_numpy

        q = BSGF("Z", ("x", "y"), Atom("R", "x", "y"),
                 all_of(Atom("S", "x"), Atom("T", "y")))
        db_np = Q.gen_db([q], n_guard=96, n_cond=64)
        svc = SGFService(catalog_from_numpy(db_np, P=2))
        assert svc.cache.metrics is svc.metrics
        assert svc.results.metrics is svc.metrics
        svc.submit([q])
        svc.tick()
        svc.submit([q])
        svc.tick()
        c = svc.counters()
        # legacy keys still served, now from the registry
        assert c["warm_queries"] == 1 and c["cold_queries"] == 1
        assert svc.metrics.counter("svc.tick.warm_queries").value == 1
        assert svc.metrics.counter("svc.result_cache.query.hit").value == 1
        assert c["query_hits"] == 1
        # per-tick accounting from the reports, one per tick
        assert c["ticks"] == 2 and len(svc.reports) == 2
        assert c["net_time"] >= 0.0 and c["total_time"] >= 0.0
        # executor metrics landed in the same registry
        assert svc.metrics.counter("msj.jobs").value > 0

    def test_ftstats_compat(self):
        from repro.ft.supervisor import FTStats

        st = FTStats()
        st.retries += 2
        st.capacity_retries += 1
        assert st.retries == 2
        assert st.as_dict()["capacity_retries"] == 1
        assert st.metrics.counter("ft.fault.reroutes").value == 2
        assert "retries=2" in repr(st)
