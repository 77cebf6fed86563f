"""The seed changes the data's tuples and answers, and none of the counts
the program sizes its arrays by."""
import json

import numpy as np
import pytest

import reference
from conftest import BENCH
from datagen import base_relations, clients, make_versions, recombine, shard

CONFIG = {**json.loads((BENCH / "configs" / "bsgf-tenants.json").read_text()),
          "rows_per_relation": 2**10}
QUERIES = [q for c in clients(CONFIG) for q in c]
SEEDS = [1, 2, 2**31 + 5]


def _unary(db):
    return [r.reshape(-1) for r in db.values() if r.shape[-1] == 1]


def test_same_seed_same_data():
    a, b = make_versions(CONFIG, 2, 2**31 + 5), make_versions(CONFIG, 2, 2**31 + 5)
    for va, vb in zip(a, b):
        assert all(np.array_equal(va[k], vb[k]) for k in va)


def test_shards_as_the_program_places_rows():
    from repro.core.relation import Relation

    rows = np.arange(64 * 4, dtype=np.int32).reshape(64, 4)
    assert np.array_equal(shard(rows, 8), np.asarray(Relation.from_numpy("R", rows, P=8).data))
    with pytest.raises(ValueError):
        shard(rows[:60], 8)


@pytest.mark.parametrize("seed", SEEDS[1:])
def test_seed_changes_tuples_and_answers(seed):
    a, b = make_versions(CONFIG, 2, SEEDS[0]), make_versions(CONFIG, 2, seed)
    for va, vb in zip(a, b):
        for name in ("R", "G"):
            assert not np.array_equal(va[name], vb[name])
        for client in clients(CONFIG)[:2]:
            ra, rb = reference.eval_request(va, client), reference.eval_request(vb, client)
            assert any(not np.array_equal(ra[k], rb[k]) for k in ra)


@pytest.mark.parametrize("seed", SEEDS[1:])
def test_seed_keeps_every_count(seed):
    """Per shard: each column's multiset, and the rows that satisfy every
    query's condition, counted."""
    a, b = make_versions(CONFIG, 2, SEEDS[0]), make_versions(CONFIG, 2, seed)
    for va, vb in zip(a, b):
        assert set(va) == set(base_relations(QUERIES))
        for name in va:
            assert va[name].shape == vb[name].shape
            assert np.array_equal(np.sort(va[name], axis=1), np.sort(vb[name], axis=1))
        for query in QUERIES:
            grel = query[2][0]
            for p in range(CONFIG["P"]):
                one = {**va, grel: va[grel][p]}, {**vb, grel: vb[grel][p]}
                ra, rb = (reference.eval_query(db, query) for db in one)
                assert len(ra) == len(rb)


def test_recombine_keeps_memberships_row_by_row():
    rng = np.random.default_rng(0)
    db = {"R": rng.integers(0, 20, (2, 50, 3)).astype(np.int32),
          "S": rng.integers(0, 20, (2, 10, 1)).astype(np.int32)}
    out = recombine(db, np.random.default_rng(1))
    assert np.array_equal(out["S"], db["S"])
    assert not np.array_equal(out["R"], db["R"])
    member = [np.isin(r["R"], r["S"]) for r in (db, out)]
    assert np.array_equal(member[0], member[1])
