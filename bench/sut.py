"""The system under test, as the benchmark drives it: ``SGFService``.

This is the only file of the benchmark that imports the program.  It
builds the program's queries from the plain ones of the configuration,
places every data version's shards on the device as resident
``Relation``s, and exposes one tick as submit → tick → wait, so that a
traffic driver can time each request from the client's side.
"""
from __future__ import annotations

import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro import compile_cache  # noqa: E402
from repro.core import algebra  # noqa: E402
from repro.core.executor import ExecutorConfig  # noqa: E402
from repro.core.relation import Relation  # noqa: E402
from repro.service import SGFService  # noqa: E402
from repro.service.catalog import Catalog  # noqa: E402

from datagen import clients  # noqa: E402


def enable_compile_cache() -> str:
    """The program's persistent compile cache, in the directory that
    ``JAX_COMPILATION_CACHE_DIR`` names (run.py sets it)."""
    return compile_cache.enable()


def to_cond(cond):
    if cond is None:
        return None
    op = cond[0]
    if op == "atom":
        return algebra.Atom(cond[1], *cond[2])
    if op == "not":
        return algebra.Not(to_cond(cond[1]))
    parts = [to_cond(c) for c in cond[1:]]
    return algebra.all_of(*parts) if op == "and" else algebra.any_of(*parts)


def to_bsgf(query) -> algebra.BSGF:
    name, out_vars, (grel, gterms), cond = query
    return algebra.BSGF(name, tuple(out_vars), algebra.Atom(grel, *gterms),
                        to_cond(cond))


class ProgramServer:
    """``SGFService`` over the configuration's data versions.

    Version ``k`` of every base relation is a device-resident ``Relation``
    built in set-up from the benchmark's ``(P, m, arity)`` shards, every
    row valid; :meth:`refresh` re-registers one, so the window copies
    nothing from the host."""

    def __init__(self, config: dict, versions: list[dict[str, np.ndarray]]):
        P = config["P"]
        self.resident = [
            {name: Relation(name, jnp.asarray(shards),
                            jnp.ones(shards.shape[:2], bool))
             for name, shards in v.items()}
            for v in versions
        ]
        jax.block_until_ready([r.data for v in self.resident for r in v.values()])
        catalog = Catalog(P=P)
        for name, rel in self.resident[0].items():
            catalog.register(name, rel)
        self.svc = SGFService(
            catalog, slots=config["slots"],
            config=ExecutorConfig(**config["executor"]),
        )
        self.clients = [[to_bsgf(q) for q in client] for client in clients(config)]

    def refresh(self, names, k: int) -> None:
        for name in names:
            self.svc.catalog.register(name, self.resident[k][name])

    def submit(self, client: int):
        return self.svc.submit(self.clients[client], tenant=client)

    def tick(self) -> None:
        self.svc.tick()

    @staticmethod
    def wait(req) -> bool:
        """Block until the request's outputs are on the device; False if
        the service failed it."""
        if not req.done:
            return False
        jax.block_until_ready([r.data for r in req.outputs.values()]
                              + [r.valid for r in req.outputs.values()])
        return True

    @staticmethod
    def fetch(req) -> dict[str, np.ndarray]:
        """The request's outputs as sorted unique host rows."""
        out = {}
        for name, rel in req.outputs.items():
            data = np.asarray(rel.data).reshape(-1, rel.arity)
            valid = np.asarray(rel.valid).reshape(-1)
            out[name] = np.unique(data[valid], axis=0).reshape(-1, rel.arity)
        return out

    def counters(self) -> dict:
        rc = self.svc.results
        return {
            "result_hits": rc.query_hits + rc.x_hits,
            "result_lookups": rc.query_hits + rc.query_misses + rc.x_hits
            + rc.x_misses,
        }

    def last_report(self):
        return self.svc.last_report

    def close(self) -> None:
        self.svc = None
        self.resident = None
