"""Plain queries and seeded data of the benchmark's configurations.

A configuration file (``bench/configs/<config>.json``) holds its clients'
queries as plain nested lists, read here into tuples; the reference reads
them as they are, and only ``sut.py`` translates them to the program's
algebra:

* a query is ``(name, out_vars, guard, cond)``;
* ``guard`` is ``(rel, terms)``; a term is a variable name or an int;
* ``cond`` is ``("atom", rel, terms)``, ``("and", c, ...)``,
  ``("or", c, ...)``, ``("not", c)`` or ``None``.

The configuration also names its data generator,
``bench/generators/<name>.py``, whose ``make`` returns one template of
every base relation.  Version ``k`` of the data is the template at
template seed ``k``, placed by the benchmark itself over the ``P`` shards
(row ``i`` on shard ``i mod P``) and then recombined by ``--seed``
(:func:`recombine`): within each shard, each column of a guard relation
is permuted among the rows whose values belong to the same unary
relations.  Every shard keeps the multiset of every column, and every
row the truth of every unary atom, so each count that a semi-join or a
Boolean condition over unary atoms depends on is the same for every
seed, while the tuples, and so the rows of every answer, change with it.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent


def as_query(obj):
    """A query read from JSON: lists become tuples, all the way down."""
    if isinstance(obj, list):
        return tuple(as_query(x) for x in obj)
    return obj


def clients(config: dict) -> list[list[tuple]]:
    """Each client's queries, in the order the client submits them."""
    return [[as_query(q) for q in client] for client in config["clients"]]


def cond_atoms(cond) -> list[tuple]:
    if cond is None:
        return []
    if cond[0] == "atom":
        return [cond]
    return [a for c in cond[1:] for a in cond_atoms(c)]


def base_relations(queries) -> dict[str, int]:
    """Relations read but not defined by ``queries``, with their arity."""
    defined = {q[0] for q in queries}
    rels: dict[str, int] = {}
    for _, _, guard, cond in queries:
        for a in [("atom",) + tuple(guard)] + cond_atoms(cond):
            if a[1] not in defined:
                rels[a[1]] = len(a[2])
    return rels


def generator(name: str):
    path = HERE / "generators" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_generator_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def shard(rows: np.ndarray, P: int) -> np.ndarray:
    """``(n, arity)`` rows as ``(P, n/P, arity)`` shards, row ``i`` on
    shard ``i mod P``."""
    n, arity = rows.shape
    if n % P:
        raise ValueError(f"{n} rows do not split evenly over {P} shards")
    return np.ascontiguousarray(rows.reshape(n // P, P, arity).transpose(1, 0, 2))


def recombine(db: dict[str, np.ndarray], rng: np.random.Generator) -> dict[str, np.ndarray]:
    """Permute each column of each relation of arity > 1 within each shard,
    among the rows whose value has the same membership in every unary
    relation of ``db``.  ``db`` maps names to ``(P, m, arity)`` shards."""
    unary = [rows.reshape(-1) for rows in db.values() if rows.shape[-1] == 1]
    out = {}
    for name, rows in sorted(db.items()):
        if rows.shape[-1] == 1:
            out[name] = rows
            continue
        new = rows.copy()
        for c in range(rows.shape[-1]):
            col = rows[:, :, c]
            member = np.zeros(col.shape, np.int64)
            for u in unary:
                member = member * 2 + np.isin(col, u)
            # rows in order of membership, a random order within each class
            order = np.lexsort((rng.random(col.shape), member), axis=1)
            # the same classes in a second random order: a permutation of
            # each class onto itself
            moved = np.lexsort((rng.random(col.shape), member), axis=1)
            shards = np.arange(col.shape[0])[:, None]
            new[shards, order, c] = col[shards, moved]
        out[name] = new
    return out


def make_versions(config: dict, n_versions: int, seed: int) -> list[dict[str, np.ndarray]]:
    """The configuration's data versions for ``seed``: ``(P, m, arity)``
    shards of every base relation, one dict per version."""
    queries = [q for c in clients(config) for q in c]
    rels = base_relations(queries)
    guards = {q[2][0] for q in queries}
    make = generator(config["generator"]).make
    rng = np.random.default_rng(seed % 2**63)
    versions = []
    for k in range(n_versions):
        template = make(rels, guards, config, k)
        placed = {name: shard(rows, config["P"]) for name, rows in template.items()}
        versions.append(recombine(placed, rng))
    return versions
