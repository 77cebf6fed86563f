"""Shuffle/compute overlap: transfer/compute sub-nodes, the comm track,
and the double-buffered forward exchange (DESIGN.md §16).

The differential contract under test: splitting every MSJ job into a
transfer sub-node (count exchange + forward all_to_all, on the dedicated
comm track) and a compute sub-node (probe + scatter, on the W cluster
slots) must leave outputs **bit-identical** to the inline path on clean,
straggler, and partial-failure runs, while the replay identities
(W=∞ == net_time, W=1 == total_time) keep holding with sub-node records
present and the happens-before sanitizer stays green while slices
overlap.  Alongside ride the sync-path regressions: tracing must not
insert per-stage barriers, traced or with spans alone, the executor
must not blanket-sync outputs (``sync_per_job`` defaults off), and a
``CapacityFault`` raised by a prefetched transfer must blame the
transfer's own retry state — never the compute occupying the slot.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import queries as Q, ref_engine
from repro.core.costmodel import (
    HADOOP,
    msj_compute_cost,
    msj_job_cost,
    msj_transfer_cost,
    stats_of_db,
)
from repro.core.executor import (
    COMM_SLOT,
    Executor,
    ExecutorConfig,
    PermanentFault,
)
from repro.core.msj import XferBuffer
from repro.core.planner import (
    ComputeJob,
    MSJJob,
    TransferJob,
    is_xfer_rel,
    job_dag,
    job_reads,
    plan_par,
    plan_sgf,
)
from repro.core.relation import db_from_dict
from repro.engine.comm import SimComm, run_pipeline
from repro.obs.tracer import Tracer
from repro.service.scheduler import SlotScheduler

P = 2


def _oracle_sgf(db_np, sgf):
    setdb = {k: {tuple(map(int, r)) for r in v} for k, v in db_np.items()}
    out = {}
    for q in sgf:
        out[q.name] = ref_engine.eval_bsgf(setdb, q)
        setdb[q.name] = out[q.name]
    return out


def _assert_env_bit_identical(env_a, env_b, names):
    for name in names:
        a, b = env_a[name], env_b[name]
        assert a.to_set() == b.to_set(), name
        np.testing.assert_array_equal(np.asarray(a.data), np.asarray(b.data))
        np.testing.assert_array_equal(np.asarray(a.valid), np.asarray(b.valid))


def _assert_replay_identities(rep):
    assert rep.net_time_by_events(None) == rep.net_time
    assert rep.net_time_by_events(1) == rep.total_time


@pytest.fixture(scope="module")
def c4_setup():
    sgf = Q.make_sgf("C4")
    db_np = Q.gen_db(sgf, n_guard=96, n_cond=96)
    return sgf, db_np, plan_sgf(sgf, "parunit")


@pytest.fixture(scope="module")
def clean_runs(c4_setup):
    """One inline and one overlapped clean execute over the same db."""
    sgf, db_np, plan = c4_setup
    out = {}
    for ov in (False, True):
        db = db_from_dict(db_np, P=P)
        ex = Executor(dict(db), SimComm(P), ExecutorConfig(overlap=ov))
        env, rep = ex.execute(plan, slots=2)
        out[ov] = (env, rep)
    return out


# --------------------------------------------------------------------------
# config + DAG shape
# --------------------------------------------------------------------------


def test_overlap_config_validation():
    with pytest.raises(ValueError, match="overlap"):
        ExecutorConfig(overlap=True, execution_mode="waves")
    with pytest.raises(ValueError, match="xfer_buffers"):
        ExecutorConfig(xfer_buffers=0)


def test_overlap_dag_splits_msj_jobs_only(c4_setup):
    _, _, plan = c4_setup
    base = job_dag(plan)
    nodes = job_dag(plan, overlap=True)
    n_msj = sum(isinstance(n.job, MSJJob) for n in base)
    xfers = [n for n in nodes if isinstance(n.job, TransferJob)]
    comps = [n for n in nodes if isinstance(n.job, ComputeJob)]
    assert n_msj > 0 and len(xfers) == len(comps) == n_msj
    assert not any(isinstance(n.job, MSJJob) for n in nodes)
    by_idx = {n.idx: n for n in nodes}
    for c in comps:
        # exactly one same-round transfer twin, ordered by an explicit edge
        twins = [x for x in xfers if x.job.buffer == c.job.buffer]
        assert len(twins) == 1 and twins[0].idx in c.deps
        assert by_idx[twins[0].idx].round_idx == c.round_idx
        assert is_xfer_rel(c.job.buffer)
        # buffer RAW is visible in the recorded access sets
        assert c.job.buffer in c.reads and c.job.buffer in twins[0].writes


def test_cost_model_prices_sub_nodes_separately(c4_setup):
    """transfer + compute == inline + one extra dispatch overhead, and the
    transfer share carries the forward bytes (so LPT and speculation
    deadlines stay meaningful per sub-node)."""
    sgf, db_np, plan = c4_setup
    stats = stats_of_db(db_from_dict(db_np, P=P))
    priced = 0
    for n in job_dag(plan):
        if not isinstance(n.job, MSJJob):
            continue
        if not all(r in stats.rels for r in job_reads(n.job)):
            continue  # later-round jobs read intermediates the base stats lack
        sjs = list(n.job.sjs)
        whole = msj_job_cost(sjs, stats, HADOOP)
        xfer = msj_transfer_cost(sjs, stats, HADOOP)
        comp = msj_compute_cost(sjs, stats, HADOOP)
        assert xfer > 0.0 and comp > 0.0
        assert xfer + comp == pytest.approx(whole + HADOOP.cost_h)
        priced += 1
    assert priced > 0


# --------------------------------------------------------------------------
# differential suite: clean / straggler / partial failure (satellite 4)
# --------------------------------------------------------------------------


def test_overlap_bit_identical_clean(c4_setup, clean_runs):
    sgf, db_np, _ = c4_setup
    (env0, rep0), (env1, rep1) = clean_runs[False], clean_runs[True]
    want = _oracle_sgf(db_np, sgf)
    names = [q.name for q in sgf]
    for q in sgf:
        assert env1[q.name].to_set() == want[q.name]
    _assert_env_bit_identical(env0, env1, names)
    # no exchange buffer may outlive its compute sub-node
    assert not any(is_xfer_rel(k) for k in env1)
    for rep in (rep0, rep1):
        _assert_replay_identities(rep)
        assert rep.event_makespan() is not None
    # transfers really ran on the comm track, computes on cluster slots
    slots_of = {
        type(r.job).__name__: set() for r in rep1.records
    }
    for r in rep1.records:
        slots_of[type(r.job).__name__].add(r.slot)
    assert slots_of["TransferJob"] == {COMM_SLOT}
    assert COMM_SLOT not in slots_of["ComputeJob"]


def test_overlap_bit_identical_straggler(c4_setup, clean_runs):
    """An injected 25x straggler on one compute sub-node must not change
    outputs, and both accountings keep the replay identities."""
    sgf, db_np, plan = c4_setup
    hit = {"n": 0}

    def ws(job, attempt):
        if isinstance(job, ComputeJob) and hit["n"] == 0:
            hit["n"] += 1
            return 25.0
        return 1.0

    db = db_from_dict(db_np, P=P)
    ex = Executor(dict(db), SimComm(P), ExecutorConfig(overlap=True))
    env, rep = ex.execute(plan, slots=2, wall_scale=ws)
    assert hit["n"] == 1
    _assert_env_bit_identical(clean_runs[False][0], env, [q.name for q in sgf])
    _assert_replay_identities(rep)


def test_overlap_partial_failure_isolate(c4_setup, clean_runs):
    """fail_policy="isolate" with sub-nodes live: poisoning one pipeline
    taints exactly its closure; surviving queries stay bit-identical."""
    sgf, db_np, plan = c4_setup
    victim = sgf.queries[0]

    def poison(job, attempt):
        base = job.base if isinstance(job, (TransferJob, ComputeJob)) else job
        sjs = getattr(base, "sjs", ())
        if any(sj.guard.rel == victim.guard.rel for sj in sjs):
            raise PermanentFault("poisoned pipeline")

    db = db_from_dict(db_np, P=P)
    ex = Executor(
        dict(db), SimComm(P),
        ExecutorConfig(overlap=True, fail_policy="isolate"),
    )
    env, rep = ex.execute(plan, slots=2, on_job=poison)
    assert rep.failed_jobs
    tainted = rep.tainted_relations()
    assert victim.name in tainted
    survivors = [q.name for q in sgf if q.name in env]
    assert survivors  # the plan is not one connected component
    _assert_env_bit_identical(clean_runs[False][0], env, survivors)
    _assert_replay_identities(rep)
    assert not any(is_xfer_rel(k) for k in env)


def test_overlap_sanitize_clean_on_chaos_tick(c4_setup):
    """The §15 gate of the tentpole: overlapping transfer/compute slices
    plus stragglers plus a partial failure, under sanitize=True — the
    happens-before clocks must stay green (the buffer edges order every
    conflicting pair)."""
    sgf, db_np, plan = c4_setup
    victim = sgf.queries[0]

    def poison(job, attempt):
        base = job.base if isinstance(job, (TransferJob, ComputeJob)) else job
        sjs = getattr(base, "sjs", ())
        if any(sj.guard.rel == victim.guard.rel for sj in sjs):
            raise PermanentFault("poisoned pipeline")

    def ws(job, attempt):
        return 10.0 if isinstance(job, TransferJob) else 1.0

    db = db_from_dict(db_np, P=P)
    stats = stats_of_db(db)
    ex = Executor(
        dict(db), SimComm(P),
        ExecutorConfig(overlap=True, fail_policy="isolate", sanitize=True,
                       speculate=True),
        stats=stats,
    )
    sched = SlotScheduler(ex, slots=2, stats=stats)
    env, rep = sched.execute(plan, on_job=poison, wall_scale=ws)
    assert ex.last_sanitize == []
    assert rep.failed_jobs  # the chaos actually happened
    _assert_replay_identities(rep)


def test_overlap_double_buffer_bound_holds(c4_setup):
    """At no instant of the virtual timeline are more than xfer_buffers
    exchanges alive (shuffled but not yet probed); with xfer_buffers=1 the
    walk degenerates to strict transfer/compute alternation per pair."""
    _, db_np, plan = c4_setup
    for n_bufs in (1, 2):
        db = db_from_dict(db_np, P=P)
        ex = Executor(
            dict(db), SimComm(P),
            ExecutorConfig(overlap=True, xfer_buffers=n_bufs),
        )
        env, rep = ex.execute(plan, slots=2)
        by_buf: dict[str, dict[str, float]] = {}
        for r in rep.records:
            if isinstance(r.job, TransferJob):
                by_buf.setdefault(r.job.buffer, {})["born"] = r.end
            elif isinstance(r.job, ComputeJob):
                by_buf.setdefault(r.job.buffer, {})["freed"] = r.end
        events = []
        for iv in by_buf.values():
            events.append((iv["born"], 1))
            events.append((iv["freed"], -1))
        alive = peak = 0
        for _, d in sorted(events, key=lambda e: (e[0], e[1])):
            alive += d
            peak = max(peak, alive)
        assert 1 <= peak <= n_bufs


# --------------------------------------------------------------------------
# satellite 1: tracing must not perturb the dispatch stream
# --------------------------------------------------------------------------


def test_traced_pipeline_identical_stream_and_bits(monkeypatch):
    """The SimComm run_pipeline path must issue the same instruction
    stream with or without a tracer — every stage runs under a profiler
    span either way, and neither inserts a per-stage barrier — and the
    carries stay bit-identical."""
    calls = {"n": 0}
    real = jax.block_until_ready

    def counting(x):
        calls["n"] += 1
        return real(x)

    monkeypatch.setattr(jax, "block_until_ready", counting)
    comm = SimComm(P)

    def stage_a(sid, carry):
        out = carry + sid.astype(jnp.float32)
        return (jnp.stack([out, out]),), out

    def stage_b(sid, carry):
        (recv,), prev = carry
        return None, prev + recv.sum(axis=0)

    x = jnp.arange(P * 4, dtype=jnp.float32).reshape(P, 4)
    traced = run_pipeline(
        comm, [stage_a, stage_b], x, tracer=Tracer(),
        names=["a", "b"],
    )
    assert calls["n"] == 0, "tracing must not sync between stages"
    plain = run_pipeline(comm, [stage_a, stage_b], x, names=["a", "b"])
    disabled = run_pipeline(
        comm, [stage_a, stage_b], x, tracer=Tracer(enabled=False),
        names=["a", "b"],
    )
    assert calls["n"] == 0, "the stages' profiler spans must not sync"
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(traced))
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(disabled))


def test_traced_execute_bit_identical_and_schedule_shaped(c4_setup, clean_runs):
    """A traced overlapped execute produces bit-identical outputs and a
    well-shaped schedule (one record per sub-node, every record obeying
    end == start + wall), with msj.xfer spans on the transfer records."""
    sgf, db_np, plan = c4_setup
    db = db_from_dict(db_np, P=P)
    ex = Executor(dict(db), SimComm(P), ExecutorConfig(overlap=True),
                  tracer=Tracer())
    env, rep = ex.execute(plan, slots=2)
    _assert_env_bit_identical(clean_runs[True][0], env, [q.name for q in sgf])
    assert rep.n_jobs == clean_runs[True][1].n_jobs
    for rec in rep.records:
        assert rec.end == pytest.approx(rec.start + rec.wall)
    xfer_spans = {
        sp.name
        for rec in rep.records if isinstance(rec.job, TransferJob)
        for root in rec.spans for sp in root.walk()
    }
    assert "msj.xfer" in xfer_spans
    _assert_replay_identities(rep)


# --------------------------------------------------------------------------
# satellite 2: no blanket output sync on the hot path
# --------------------------------------------------------------------------


def test_no_output_sync_by_default(c4_setup, monkeypatch):
    """With overlap on and the default config, the executor must never
    block on a job's outputs — the only per-job sync is the overflow
    scalar.  sync_per_job=True remains available as a measurement mode."""
    _, db_np, plan = c4_setup
    calls = {"n": 0}
    real = jax.block_until_ready

    def counting(x):
        calls["n"] += 1
        return real(x)

    monkeypatch.setattr(jax, "block_until_ready", counting)
    db = db_from_dict(db_np, P=P)
    cfg = ExecutorConfig(overlap=True)
    assert cfg.sync_per_job is False
    Executor(dict(db), SimComm(P), cfg).execute(plan, slots=2)
    assert calls["n"] == 0

    db = db_from_dict(db_np, P=P)
    Executor(
        dict(db), SimComm(P), ExecutorConfig(overlap=True, sync_per_job=True)
    ).execute(plan, slots=2)
    assert calls["n"] > 0


# --------------------------------------------------------------------------
# satellite 3: prefetch overflow blames the transfer, not the compute
# --------------------------------------------------------------------------


def test_prefetch_overflow_blamed_on_transfer(c4_setup, clean_runs):
    """Deliberate undersizing (cap_slack < 1) makes prefetched transfers
    overflow.  The capacity ladder must run on the transfer sub-nodes'
    own RetryStates (attempts land on transfer records, never on the
    compute occupying the slot), outputs stay bit-identical, and the
    ExecutorConfig is never mutated by the ladder."""
    sgf, db_np, plan = c4_setup
    db = db_from_dict(db_np, P=P)
    cfg = ExecutorConfig(overlap=True, cap_slack=0.02)
    before = dataclasses.asdict(cfg)
    ex = Executor(dict(db), SimComm(P), cfg)
    env, rep = ex.execute(plan, slots=2)
    assert ex.ft_counters["overflow_retries"] >= 1
    retried = [r for r in rep.records if r.attempts > 1]
    assert retried and all(
        isinstance(r.job, TransferJob) for r in retried
    ), "capacity retries must land on transfer records"
    assert all(
        r.attempts == 1 for r in rep.records if isinstance(r.job, ComputeJob)
    )
    _assert_env_bit_identical(clean_runs[False][0], env, [q.name for q in sgf])
    assert dataclasses.asdict(cfg) == before  # ladder never mutates config


def test_prefetch_capacity_fault_isolates_transfer(c4_setup):
    """With retries exhausted, the CapacityFault is pinned on the transfer
    sub-node: the failed records are TransferJobs, their compute twins are
    tainted (never dispatched), and no ComputeJob is ever blamed."""
    _, db_np, plan = c4_setup
    db = db_from_dict(db_np, P=P)
    ex = Executor(
        dict(db), SimComm(P),
        ExecutorConfig(overlap=True, cap_slack=1e-6, max_retries=0,
                       fail_policy="isolate"),
    )
    env, rep = ex.execute(plan, slots=2)
    assert rep.failed_jobs
    assert all(isinstance(r.job, TransferJob) for r in rep.failed_jobs)
    tainted_kinds = {type(r.job).__name__ for r in rep.tainted_jobs}
    assert "ComputeJob" in tainted_kinds
    _assert_replay_identities(rep)


# --------------------------------------------------------------------------
# satellite 6 (unit level): deleted buffer edges are killed, 0 false pos
# --------------------------------------------------------------------------


def test_buffer_edge_deletion_is_killed(c4_setup):
    from repro.analysis.verifier import errors, verify_nodes, verify_plan

    _, _, plan = c4_setup
    nodes = job_dag(plan, overlap=True)
    assert not errors(verify_plan(plan, nodes=nodes))  # 0 false positives
    assert not verify_nodes(nodes)
    killed = 0
    for n in nodes:
        if not isinstance(n.job, ComputeJob):
            continue
        twin = next(
            x.idx for x in nodes
            if isinstance(x.job, TransferJob) and x.job.buffer == n.job.buffer
        )
        mutated = tuple(
            dataclasses.replace(m, deps=frozenset(m.deps) - {twin})
            if m.idx == n.idx else m
            for m in nodes
        )
        assert errors(verify_plan(plan, nodes=mutated)), (
            f"deleted transfer→compute edge {twin}->{n.idx} survived"
        )
        assert verify_nodes(mutated)
        killed += 1
    assert killed > 0


# --------------------------------------------------------------------------
# narrow/taint semantics of the sub-kinds
# --------------------------------------------------------------------------


def test_overlap_on_multi_query_plan_bit_identical():
    """BSGF batch (plan_par) under overlap: same outputs, transfers on the
    comm track, and job_reads of a compute includes its buffer."""
    qs = Q.make_queries("A4")
    db_np = Q.gen_db(qs, n_guard=96, n_cond=96)
    env0, _ = Executor(
        db_from_dict(db_np, P=P), SimComm(P), ExecutorConfig()
    ).execute(plan_par(qs), slots=2)
    ex = Executor(
        db_from_dict(db_np, P=P), SimComm(P), ExecutorConfig(overlap=True)
    )
    env1, rep1 = ex.execute(plan_par(qs), slots=2)
    names = [q.name for q in qs]
    _assert_env_bit_identical(env0, env1, names)
    for n in job_dag(plan_par(qs), overlap=True):
        if isinstance(n.job, ComputeJob):
            assert n.job.buffer in job_reads(n.job)
