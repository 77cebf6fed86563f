"""EVAL's count-sized forward shuffle (DESIGN.md §6): the bucket capacity
comes from the per-(source, destination) row counts instead of the
worst-case sum of the inputs' capacities, and nothing else changes."""
import numpy as np
import pytest

from repro.core import eval_op
from repro.core.algebra import And, Atom, Not, Or, eval_cond
from repro.core.eval_op import EvalUnit, _unit_salt, run_eval
from repro.core.relation import Relation
from repro.engine import hashing
from repro.engine.comm import SimComm

P = 8


def _rel(name, rows, arity):
    rows = np.asarray(rows, np.int64).reshape(-1, arity)
    if len(rows) == 0:
        return Relation.empty(name, arity, P=P, cap=4)
    return Relation.from_numpy(name, rows, P=P)


def _unit(name, x0, xs, arity, cond_of):
    atoms = tuple(Atom(x, *[f"v{k}" for k in range(arity)]) for x in xs)
    return EvalUnit(name, x0, tuple(xs), atoms, cond_of(atoms) if atoms else None)


def _case_random(rng):
    """Three units of arity 2 over random, overlapping relations."""
    base = rng.integers(0, 40, (300, 2))
    env, units = {}, []
    for u in range(3):
        x0 = f"G{u}"
        env[x0] = _rel(x0, base[rng.random(len(base)) < 0.6], 2)
        xs = [f"X{u}_{i}" for i in range(3)]
        for x in xs:
            env[x] = _rel(x, base[rng.random(len(base)) < 0.4], 2)
        units.append(_unit(f"Z{u}", x0, xs, 2,
                           lambda a: Or(And(a[0], Not(a[1])), a[2])))
    return env, units


def _case_empty(rng):
    """An empty conditional input beside an empty guard projection."""
    rows = rng.integers(0, 30, (120, 2))
    env = {"G0": _rel("G0", rows, 2), "X0": _rel("X0", [], 2),
           "X1": _rel("X1", rows[:50], 2), "G1": _rel("G1", [], 2)}
    units = [_unit("Z0", "G0", ["X0", "X1"], 2, lambda a: Or(a[0], Not(a[1]))),
             _unit("Z1", "G1", ["X1"], 2, lambda a: a[0])]
    return env, units


def _case_same_tuples(rng):
    """x0 and every X_i hold the same tuples: every row of a group lands
    on one shard, and every group has all its members."""
    rows = np.unique(rng.integers(0, 50, (200, 3)), axis=0)
    env = {n: _rel(n, rows, 3) for n in ("G", "X0", "X1", "X2")}
    units = [_unit("Z", "G", ["X0", "X1", "X2"], 3,
                   lambda a: And(a[0], And(a[1], a[2])))]
    return env, units


def _case_single_unit(rng):
    """One unit, one conditional atom."""
    env = {"G": _rel("G", rng.integers(0, 20, (90, 2)), 2),
           "X": _rel("X", rng.integers(0, 20, (60, 2)), 2)}
    return env, [_unit("Z", "G", ["X"], 2, lambda a: Not(a[0]))]


def _case_arity_padding(rng):
    """Units of arity 1 and 2 beside one of arity 3: the narrower tuples
    are padded to A = 3 columns."""
    env, units = {}, []
    for arity in (1, 2, 3):
        rows = rng.integers(0, 12, (150, arity))
        env[f"G{arity}"] = _rel(f"G{arity}", rows, arity)
        env[f"X{arity}"] = _rel(f"X{arity}", rows[rng.random(len(rows)) < 0.5],
                                arity)
        units.append(_unit(f"Z{arity}", f"G{arity}", [f"X{arity}"], arity,
                           lambda a: a[0]))
    return env, units


CASES = {
    "random": _case_random,
    "empty_input": _case_empty,
    "same_tuples": _case_same_tuples,
    "single_unit": _case_single_unit,
    "arity_padding": _case_arity_padding,
}


def _largest_pair_count(env, units) -> int:
    """The most valid rows one shard sends one destination, from the
    inputs' placement and the units' routing hash."""
    total = np.zeros((P, P), np.int64)
    for u in units:
        for name in (u.x0, *u.xs):
            rel = env[name]
            for src in range(P):
                tup = rel.data[src]
                dest = np.asarray(hashing.bucket_of(
                    hashing.hash_cols(tup, salt=_unit_salt(u.name)), P))
                ok = np.asarray(rel.valid[src])
                total[src] += np.bincount(dest[ok], minlength=P)
    return int(total.max())


def _want(env, units):
    """The reducer's semantics on host sets: ``x0 ∧ φ(X_1..X_n)``."""
    out = {}
    for u in units:
        xs = {a: env[x].to_set() for a, x in zip(u.atoms, u.xs)}
        rows = set()
        for t in env[u.x0].to_set():
            leaf = {a: t in s for a, s in xs.items()}
            if u.cond is None or eval_cond(u.cond, leaf):
                rows.add(t)
        out[u.name] = rows
    return out


@pytest.mark.parametrize("case", sorted(CASES))
def test_count_sized_eval_matches_bound(case, monkeypatch):
    env, units = CASES[case](np.random.default_rng(7))
    reads = []
    real = eval_op.host_read

    def counting(x, site):
        reads.append(site)
        return real(x, site)

    monkeypatch.setattr(eval_op, "host_read", counting)
    outs, stats = run_eval(env, units, SimComm(P))
    assert reads == ["eval.count_cap"]
    reads.clear()
    base, base_stats = run_eval(env, units, SimComm(P), count_sized=False)
    assert reads == []

    want = _want(env, units)
    for u in units:
        assert outs[u.name].to_set() == base[u.name].to_set() == want[u.name]

    bound = sum(env[n].cap for u in units for n in (u.x0, *u.xs))
    assert base_stats["forward_cap"] == bound
    cap = stats["forward_cap"]
    top = _largest_pair_count(env, units)
    assert top <= cap <= bound
    assert cap & (cap - 1) == 0, cap  # a power of two
    assert cap == 1 or cap // 2 < top  # the least power of two that holds it
    assert int(stats["overflow"]) == int(base_stats["overflow"]) == 0
    assert int(stats["sent_fwd"]) == int(base_stats["sent_fwd"])
    assert int(stats["recv_fwd"]) == int(base_stats["recv_fwd"])
    # the counts read are charged to the forward bytes, P int32 per shard
    assert int(stats["bytes_fwd"]) == int(base_stats["bytes_fwd"]) + P * P * 4


def test_override_sets_cap_and_reports_overflow():
    """``forward_cap`` overrides the count, whose bytes are then not
    charged; a cap under the largest pair count reports overflow."""
    env, units = _case_same_tuples(np.random.default_rng(3))
    top = _largest_pair_count(env, units)
    _, stats = run_eval(env, units, SimComm(P), forward_cap=top - 1)
    assert stats["forward_cap"] == top - 1
    assert int(stats["overflow"]) > 0
    _, stats = run_eval(env, units, SimComm(P), forward_cap=top)
    assert int(stats["overflow"]) == 0
    assert int(stats["bytes_fwd"]) == int(stats["sent_fwd"]) * (3 + 2) * 4
