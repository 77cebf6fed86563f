"""Plain NumPy reference for the benchmark's queries.

Independent of the program: it reads the plain queries of the configuration
and the host copy of each data version, and nothing the program made.
A copy of the program's ``ref_engine.eval_bsgf_np`` (set semantics,
conditions that are Boolean combinations of unary atoms over guard
variables), extended to nested SGF by evaluating the queries of a request
in order, each output readable by the later ones.
"""
from __future__ import annotations

from typing import Mapping

import numpy as np

from datagen import cond_atoms


def _truth(cond, leaf: Mapping[tuple, np.ndarray]) -> np.ndarray:
    op = cond[0]
    if op == "atom":
        return leaf[cond]
    if op == "not":
        return ~_truth(cond[1], leaf)
    parts = [_truth(c, leaf) for c in cond[1:]]
    out = parts[0]
    for p in parts[1:]:
        out = (out & p) if op == "and" else (out | p)
    return out


def eval_query(db: Mapping[str, np.ndarray], query) -> np.ndarray:
    """One query's output as sorted unique int32 rows."""
    _, out_vars, (grel, gterms), cond = query
    guard = np.asarray(db[grel]).reshape(-1, len(gterms))
    keep = np.ones(guard.shape[0], bool)
    col_of: dict[str, int] = {}
    for i, t in enumerate(gterms):
        if isinstance(t, int):
            keep &= guard[:, i] == t
        elif t in col_of:
            keep &= guard[:, i] == guard[:, col_of[t]]
        else:
            col_of[t] = i
    leaf = {}
    for a in cond_atoms(cond):
        _, rel, terms = a
        if len(terms) != 1 or terms[0] not in col_of:
            raise ValueError(f"{a} is not a unary atom over a guard variable")
        members = np.asarray(db[rel]).reshape(-1)
        leaf[a] = np.isin(guard[:, col_of[terms[0]]], members)
    if cond is not None:
        keep &= _truth(cond, leaf)
    out = guard[keep][:, [col_of[v] for v in out_vars]].astype(np.int32)
    return np.unique(out, axis=0).reshape(-1, len(out_vars))


def eval_request(db: Mapping[str, np.ndarray], queries) -> dict[str, np.ndarray]:
    """Every output of one request, evaluated in order."""
    env = dict(db)
    out = {}
    for q in queries:
        out[q[0]] = env[q[0]] = eval_query(env, q)
    return out
