"""Executor + fault-tolerance tests: capacity retry, fault injection,
straggler re-dispatch, checkpoint restart equivalence, elastic rescale."""
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import queries as Q, ref_engine
from repro.core.costmodel import HADOOP, stats_of_db
from repro.core.executor import Executor, ExecutorConfig, execute_plan
from repro.core.planner import plan_greedy, plan_par
from repro.core.relation import db_from_dict
from repro.engine.comm import SimComm
from repro.ft import elastic, supervisor


def _want(qs, db_np):
    setdb = {k: {tuple(map(int, r)) for r in v} for k, v in db_np.items()}
    out = {}
    for q in qs:
        out[q.name] = ref_engine.eval_bsgf(setdb, q)
        setdb[q.name] = out[q.name]
    return out


def test_supervisor_retries_injected_faults(rng):
    qs = Q.make_queries("A1")
    db_np = Q.gen_db(qs, n_guard=200, n_cond=200)
    db = db_from_dict(db_np, P=2)
    plan = plan_par(qs)
    ex = Executor(db, SimComm(2))
    sup = supervisor.Supervisor(ex, supervisor.FTConfig(fault_rate=0.4, seed=1))
    env, report = sup.execute(plan)
    want = _want(qs, db_np)
    assert env["Z"].to_set() == want["Z"]
    assert sup.stats.faults_injected > 0
    assert sup.stats.retries >= sup.stats.faults_injected


def test_supervisor_gives_up_after_max_restarts():
    qs = Q.make_queries("A3")
    db = db_from_dict(Q.gen_db(qs, n_guard=64, n_cond=64), P=2)
    ex = Executor(db, SimComm(2))
    sup = supervisor.Supervisor(
        ex, supervisor.FTConfig(fault_rate=1.0, max_restarts=2, seed=0)
    )
    with pytest.raises(supervisor.SimulatedFault):
        sup.execute(plan_par(qs))


def test_capacity_fault_retry_path(rng):
    """Undersized buffers trigger CapacityFault; executor retry fixes it."""
    qs = Q.make_queries("A3")
    db_np = Q.gen_db(qs, n_guard=256, n_cond=256)
    db = db_from_dict(db_np, P=4)
    cfgx = ExecutorConfig(cap_slack=0.01, max_retries=3)
    env, report = execute_plan(db, plan_par(qs), SimComm(4), cfgx)
    want = _want(qs, db_np)
    assert env["Z"].to_set() == want["Z"]
    assert any(r.attempts > 1 for r in report.records)


def test_probe_backend_validated_eagerly():
    """A bad probe_backend fails at config construction, listing the valid
    names, instead of deep inside resolve_probe_backend at job time."""
    from repro.core.executor import PROBE_BACKENDS, resolve_probe_backend

    with pytest.raises(ValueError, match="sorted, pallas, dense"):
        ExecutorConfig(probe_backend="bogus")
    for name in PROBE_BACKENDS:
        assert ExecutorConfig(probe_backend=name).probe_backend == name
        assert callable(resolve_probe_backend(name))
    with pytest.raises(ValueError, match="valid names"):
        resolve_probe_backend("bogus")


def test_overflow_retry_state_machine():
    """cap_slack < 1 overflow path: the first retry clears the slack (cap
    stays count-sized), a second overflow doubles the observed capacity,
    and the attempt count lands on the JobRecord."""
    from repro.core.planner import MSJJob

    qs = Q.make_queries("A3")
    db_np = Q.gen_db(qs, n_guard=64, n_cond=64)
    db = db_from_dict(db_np, P=2)
    seen = []

    class FlakyExecutor(Executor):
        def run_job(self, job, *, cap_override=None, cap_slack=None):
            outs, stats = super().run_job(
                job, cap_override=cap_override, cap_slack=cap_slack
            )
            if isinstance(job, MSJJob):
                seen.append((cap_override, cap_slack))
                if len(seen) <= 2:  # force overflow on the first two attempts
                    stats = dict(stats)
                    stats["overflow"] = 5
                    stats["forward_cap"] = 2048
            return outs, stats

    config = ExecutorConfig(cap_slack=0.5, max_retries=3)
    ex = FlakyExecutor(db, SimComm(2), config)
    env, report = ex.execute(plan_greedy(qs, stats_of_db(db, default_sel=0.5)))
    msj_recs = [r for r in report.records if isinstance(r.job, MSJJob)]
    assert [r.attempts for r in msj_recs] == [3]
    # attempt 1 ran undersized; retry 1 cleared the slack without a cap
    # override; retry 2 doubled the observed capacity
    assert seen[0] == (None, None)
    assert seen[1] == (None, 1.0)
    assert seen[2] == (4096, 1.0)
    want = _want(qs, db_np)
    assert env["Z"].to_set() == want["Z"]


def test_overflow_retry_does_not_mutate_config():
    """The slack relaxation is scoped to the retried job: the executor's
    config object (and its cap_slack) must be unchanged afterwards, so
    deliberate undersizing stays in force for later jobs and plans."""
    from repro.core.planner import MSJJob

    qs = Q.make_queries("A3")
    db = db_from_dict(Q.gen_db(qs, n_guard=256, n_cond=256), P=4)
    config = ExecutorConfig(cap_slack=0.01, max_retries=3)
    ex = Executor(db, SimComm(4), config)
    env, report = ex.execute(plan_par(qs))
    assert any(r.attempts > 1 for r in report.records)  # the retry fired
    assert ex.config is config  # not swapped out behind the caller's back
    assert config.cap_slack == 0.01


def test_overflow_exhausts_retries_raises_capacity_fault():
    from repro.core.executor import CapacityFault
    from repro.core.planner import MSJJob

    qs = Q.make_queries("A3")
    db = db_from_dict(Q.gen_db(qs, n_guard=64, n_cond=64), P=2)

    class AlwaysOverflow(Executor):
        def run_job(self, job, *, cap_override=None, cap_slack=None):
            outs, stats = super().run_job(
                job, cap_override=cap_override, cap_slack=cap_slack
            )
            if isinstance(job, MSJJob):
                stats = dict(stats)
                stats["overflow"] = 1
            return outs, stats

    ex = AlwaysOverflow(db, SimComm(2), ExecutorConfig(cap_slack=0.5, max_retries=1))
    with pytest.raises(CapacityFault, match="overflow"):
        ex.execute(plan_greedy(qs, stats_of_db(db)))


def test_supervisor_accumulates_stats_when_execute_raises():
    """Regression: FTStats accumulation lives in a ``finally`` — the
    capacity retries that led up to an aborting CapacityFault must still
    be accounted (they happened), and the policy-extended config must be
    restored on the raise path."""
    from repro.core.executor import CapacityFault
    from repro.core.planner import MSJJob

    qs = Q.make_queries("A3")
    db = db_from_dict(Q.gen_db(qs, n_guard=64, n_cond=64), P=2)

    class AlwaysOverflow(Executor):
        def run_job(self, job, *, cap_override=None, cap_slack=None):
            outs, stats = super().run_job(
                job, cap_override=cap_override, cap_slack=cap_slack
            )
            if isinstance(job, MSJJob):
                stats = dict(stats)
                stats["overflow"] = 1
            return outs, stats

    base = ExecutorConfig(cap_slack=0.5, max_retries=2)
    ex = AlwaysOverflow(db, SimComm(2), base)
    sup = supervisor.Supervisor(ex, supervisor.FTConfig(fault_rate=0.0, seed=0))
    with pytest.raises(CapacityFault, match="overflow"):
        sup.execute(plan_greedy(qs, stats_of_db(db)))
    assert sup.stats.capacity_retries >= base.max_retries
    assert ex.config is base  # caller's config restored despite the raise


def test_elastic_repartition_preserves_results(rng):
    qs = Q.make_queries("A1")
    db_np = Q.gen_db(qs, n_guard=200, n_cond=200)
    want = _want(qs, db_np)
    db4 = db_from_dict(db_np, P=4)
    env4, _ = execute_plan(db4, plan_par(qs), SimComm(4))
    # scale down to P=2 (node loss), rerun
    db2 = elastic.repartition_db(db4, 2)
    env2, _ = execute_plan(db2, plan_par(qs), SimComm(2))
    assert env4["Z"].to_set() == env2["Z"].to_set() == want["Z"]


@pytest.mark.slow
def test_train_crash_restart_bitexact():
    from repro.configs import get_config
    from repro.data import synthetic
    from repro.train import optimizer, train_step as ts

    cfg = get_config("qwen3-0.6b", smoke=True)
    opt_cfg = optimizer.OptConfig(lr=1e-3, warmup_steps=2, total_steps=20)
    step_fn = jax.jit(ts.make_train_step(cfg, opt_cfg))
    bf = synthetic.make_batch_fn(cfg, 2, 32)
    with tempfile.TemporaryDirectory() as d:
        st = ts.init_state(cfg, jax.random.PRNGKey(0), opt_cfg)
        with pytest.raises(supervisor.SimulatedFault):
            supervisor.run_train_loop(st, step_fn, bf, steps=8, ckpt_dir=d,
                                      ckpt_every=2, crash_at=5)
        st2 = ts.init_state(cfg, jax.random.PRNGKey(0), opt_cfg)
        st2, _ = supervisor.run_train_loop(st2, step_fn, bf, steps=8, ckpt_dir=d,
                                           ckpt_every=2)
        st3 = ts.init_state(cfg, jax.random.PRNGKey(0), opt_cfg)
        for i in range(8):
            st3, _ = step_fn(st3, bf(i))
        for a, b in zip(jax.tree.leaves(st2["params"]), jax.tree.leaves(st3["params"])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_checkpoint_reshard_on_load():
    from repro.ckpt import checkpoint
    from repro.configs import get_config
    from repro.models import model

    cfg = get_config("qwen3-0.6b", smoke=True)
    params = model.init_params(cfg, jax.random.PRNGKey(0))
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    with tempfile.TemporaryDirectory() as d:
        checkpoint.save(d, 1, params, mesh=mesh)
        assert checkpoint.latest_step(d) == 1
        specs = model.partition_specs(cfg, params, mesh)
        loaded = checkpoint.load(d, 1, params, mesh=mesh, specs=specs)
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(loaded)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_atomicity(tmp_path):
    """A crash mid-write must not corrupt the latest complete checkpoint."""
    import os

    from repro.ckpt import checkpoint

    tree = {"a": jnp.ones((4,)), "b": {"c": jnp.zeros((2, 2))}}
    checkpoint.save(str(tmp_path), 1, tree)
    # simulate a torn write of step 2
    os.makedirs(tmp_path / "step_00000002.tmp", exist_ok=True)
    (tmp_path / "step_00000002.tmp" / "a.npy").write_bytes(b"garbage")
    assert checkpoint.latest_step(str(tmp_path)) == 1
    loaded = checkpoint.load(str(tmp_path), 1, tree)
    np.testing.assert_array_equal(np.asarray(loaded["a"]), np.ones((4,)))


def test_eval_overflow_climbs_the_retry_ladder():
    """An EVAL job forced to an undersized bucket reports overflow; the
    RetryState ladder doubles the cap it ran at until every row fits, and
    the outputs are the sound ones."""
    from repro.core.planner import EvalJob

    qs = Q.make_queries("A3")
    db_np = Q.gen_db(qs, n_guard=64, n_cond=64)
    seen = []

    class Undersized(Executor):
        def run_job(self, job, *, cap_override=None, cap_slack=None):
            if isinstance(job, EvalJob) and cap_override is None:
                cap_override = 1
            outs, stats = super().run_job(
                job, cap_override=cap_override, cap_slack=cap_slack
            )
            if isinstance(job, EvalJob):
                seen.append((stats["forward_cap"], int(stats["overflow"])))
            return outs, stats

    ex = Undersized(db_from_dict(db_np, P=4), SimComm(4),
                    ExecutorConfig(max_retries=12))
    env, report = ex.execute(plan_par(qs))
    caps = [c for c, _ in seen]
    assert caps == [2 ** i for i in range(len(caps))] and len(caps) > 1
    assert all(o > 0 for _, o in seen[:-1]) and seen[-1][1] == 0
    rec = next(r for r in report.records if isinstance(r.job, EvalJob))
    assert rec.attempts == len(caps)
    assert ex.ft_counters["overflow_retries"] == len(caps) - 1
    assert env["Z"].to_set() == _want(qs, db_np)["Z"]


@pytest.mark.parametrize("count_sized", [True, False])
def test_eval_cap_follows_count_sized(count_sized, monkeypatch):
    """``ExecutorConfig.count_sized`` governs EVAL as it does MSJ: on, one
    ``eval.count_cap`` read sizes the buckets; off, no read and the cap is
    the sum of the EVAL inputs' capacities."""
    from repro.core import eval_op
    from repro.core.planner import EvalJob

    qs = Q.make_queries("A3")
    db_np = Q.gen_db(qs, n_guard=128, n_cond=128)
    sites = []
    real = eval_op.host_read
    monkeypatch.setattr(
        eval_op, "host_read", lambda x, site: sites.append(site) or real(x, site)
    )
    ex = Executor(db_from_dict(db_np, P=4), SimComm(4),
                  ExecutorConfig(count_sized=count_sized))
    env, report = ex.execute(plan_par(qs))
    (rec,) = [r for r in report.records if isinstance(r.job, EvalJob)]
    bound = sum(
        env[q.guard.rel].cap + sum(env[x].cap for x in xin)
        for q, xin in zip(rec.job.queries, rec.job.atom_inputs)
    )
    if count_sized:
        assert sites == ["eval.count_cap"]
        assert rec.stats["forward_cap"] < bound
    else:
        assert sites == []
        assert rec.stats["forward_cap"] == bound
    assert env["Z"].to_set() == _want(qs, db_np)["Z"]
