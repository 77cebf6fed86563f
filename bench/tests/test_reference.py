"""bench/reference.py agrees with the program's set-semantics oracle
(``ref_engine.eval_sgf``, a loop per guard tuple) at a small size, on
every client of every configuration and on the paper's nested SGF
families C1, C2 and C4 (Figure 6), which need the reference's nesting."""
import json

import numpy as np
import pytest

import reference
from conftest import BENCH
from datagen import as_query, base_relations, clients, generator

XYZW = ["x", "y", "z", "w"]


def _star(name, guard, conds):
    return [name, XYZW, [guard, XYZW], ["and"] + conds]


def _a(rel, var):
    return ["atom", rel, [var]]


NESTED = {
    "C1": [_star(f"Z{i}", f"G{i}", [_a("S", "x"), _a("T", "y")]) for i in range(1, 5)],
    "C2": [_star(f"Z{i}", f"G{i}", [_a("STUVS"[i - 1], "x"), _a("STUVS"[i], "y")])
           for i in range(1, 5)],
    "C4": [_star("Z1", "G1", [_a("S", "x"), _a("T", "y")]),
           _star("Z2", "G2", [_a("S", "x"), _a("T", "y")]),
           _star("Z3", "Z1", [_a("U", "z"), _a("V", "w")]),
           _star("Z4", "Z2", [_a("U", "z"), _a("V", "w")])],
}
CASES = [(f"{path.stem}:{i}", client)
         for path in sorted((BENCH / "configs").glob("*.json"))
         for i, client in enumerate(clients(json.loads(path.read_text())))]
CASES += [(k, [as_query(q) for q in v]) for k, v in NESTED.items()]


def _db(queries, data, n=96, domain=40, seed=3):
    """The paper generator's data, or unary conditions drawn independently
    of each other, so that B2's "exactly one" holds on some rows (the
    paper generator makes every condition hold on the same low half of
    the domain)."""
    rels = base_relations(queries)
    guards = {q[2][0] for q in queries}
    db = generator("paper").make(rels, guards, {"rows_per_relation": n, "sel": 0.5}, seed)
    if data == "independent":
        rng = np.random.default_rng(seed)
        for name, rows in db.items():
            shape = (n // 4, 1) if rows.shape[1] == 1 else rows.shape
            db[name] = rng.integers(0, domain, shape).astype(np.int32)
    return db


@pytest.mark.parametrize("data", ["paper", "independent"])
@pytest.mark.parametrize("case", CASES, ids=[c[0] for c in CASES])
def test_reference_matches_program_oracle(case, data):
    from repro.core import ref_engine
    from repro.core.algebra import SGF

    from sut import to_bsgf

    fid, queries = case
    db = _db(queries, data)
    got = reference.eval_request(db, queries)
    setdb = {k: {tuple(int(x) for x in r) for r in v} for k, v in db.items()}
    want = ref_engine.eval_sgf(setdb, SGF([to_bsgf(q) for q in queries]))
    assert set(got) == set(want)
    for name, rows in got.items():
        assert {tuple(int(x) for x in r) for r in rows} == want[name]
        assert np.array_equal(rows, np.unique(rows, axis=0))
    if data == "independent" or fid != "bsgf-tenants:8":  # B2 may be empty
        assert sum(len(r) for r in got.values()) > 0
