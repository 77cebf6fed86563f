"""The paper's synthetic database (arXiv:1605.05219 Sec. 5), copied from
the program's ``repro.core.queries.gen_db`` so that no change to the
program can move the data.

Guard columns are uniform over ``[0, domain)``, ``domain`` a quarter of
the rows; a unary conditional relation draws a ``sel`` fraction of its
tuples from ``[0, sel·domain)`` and the rest from the disjoint
``[domain, 2·domain)``, so about ``sel`` of the guard tuples match each
condition: the paper's selectivity.  Configuration keys:
``rows_per_relation`` (guards and conditions alike) and ``sel``.
"""
from __future__ import annotations

import numpy as np


def make(relations: dict[str, int], guards: set[str], config: dict,
         seed: int) -> dict[str, np.ndarray]:
    """``(n, arity)`` int32 rows of every relation in ``relations``
    (name -> arity), drawn from ``seed``."""
    n = config["rows_per_relation"]
    sel = config["sel"]
    rng = np.random.default_rng(seed)
    domain = max(n // 4, 16)
    db: dict[str, np.ndarray] = {}
    for name, arity in sorted(relations.items()):
        if name in guards:
            db[name] = rng.integers(0, domain, (n, arity)).astype(np.int32)
        else:
            lo = max(1, int(round(domain * sel)))
            n_match = int(round(n * sel))
            key_col = np.concatenate([
                rng.integers(0, lo, n_match),
                rng.integers(domain, 2 * domain, n - n_match),
            ])
            rng.shuffle(key_col)
            cols = [key_col] + [rng.integers(0, domain, n) for _ in range(arity - 1)]
            db[name] = np.stack(cols, axis=1).astype(np.int32)
    return db
