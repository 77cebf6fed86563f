"""Zipf skew ladder: heavy-hitter splitting vs key-distribution skew.

The skew defense's acceptance benchmark (DESIGN.md §17): one single-key
semi-join whose *probe* (guard) key column is drawn from a Zipf
distribution of increasing exponent, run undefended and defended at each
rung.  Without the defense the count-sized forward capacity — the max
per-destination bucket the shuffle must provision, i.e. the collective's
straggler term — grows with the hottest key's multiplicity.  With the
defense the planner's hitter evidence (``stats_of_db(...,
heavy_hitters=k)``) annotates the job, the profile sub-node salts the
hot probe keys over R sub-shards and replicates their build rows, and
the capacity stays near the uniform rung's.

Acceptance (committed into ``BENCH_msj.json`` and gated by
``benchmarks.regression``):

* ``zipf_bit_identical`` — every defended run returns bit-identical
  output to its undefended twin (the defense is a routing change, never
  a semantics change);
* ``zipf_flat`` — the defended forward capacity at every exponent stays
  within ``FLAT_TOL`` (1.15x) of the uniform (exponent-0) rung, even as
  the undefended capacity departs.

Wall-clock ``net_s``/``total_s`` ride along as timed (tolerance-gated)
metrics; the acceptance itself is deterministic — capacity and the
chosen R are functions of the seeded data, not machine speed.
"""
from __future__ import annotations

import numpy as np

from repro.core.algebra import BSGF, Atom
from repro.core.costmodel import stats_of_db
from repro.core.executor import Executor, ExecutorConfig
from repro.core.planner import EvalJob, MSJJob, annotate_skew, plan_par
from repro.core.relation import db_from_dict
from repro.engine.comm import SimComm

#: Zipf exponents, uniform first — the flatness gate's reference rung
EXPONENTS = (0.0, 0.5, 1.0, 1.5)

#: defended capacity must stay within this factor of the uniform rung
FLAT_TOL = 1.15

COLS = ("exponent", "variant", "bytes_shuffled", "forward_cap", "R",
        "hot_keys", "replicated", "net_s", "total_s", "bit_identical")


def _zipf_column(rng: np.random.Generator, n: int, domain: int,
                 s: float) -> np.ndarray:
    """n draws from a rank-frequency Zipf(s) law over [0, domain)."""
    ranks = np.arange(1, domain + 1, dtype=np.float64)
    p = ranks ** -s
    return rng.choice(domain, size=n, p=p / p.sum()).astype(np.int32)


def _rows_of(env, name: str) -> list[tuple[int, ...]]:
    rel = env[name]
    rows = np.asarray(rel.data)[np.asarray(rel.valid)]
    return sorted(map(tuple, rows.tolist()))


def run(n_guard: int = 4096, P: int = 8, seed: int = 7) -> list[dict]:
    """Execute the ladder; two dicts (undefended, defended) per exponent."""
    q = BSGF("zout", ("v0", "v1"), Atom("R", "v0", "v1"), Atom("S", "v0", "v2"))
    domain = max(n_guard // 8, 16)
    out: list[dict] = []
    for s in EXPONENTS:
        rng = np.random.default_rng(seed)  # same payloads, only keys reshaped
        R = np.stack(
            [_zipf_column(rng, n_guard, domain, s),
             rng.integers(0, 1 << 20, n_guard).astype(np.int32)], axis=1
        )
        S = np.stack(
            [rng.integers(0, domain, n_guard // 4).astype(np.int32),
             rng.integers(0, 1 << 20, n_guard // 4).astype(np.int32)], axis=1
        )
        db = db_from_dict({"R": R, "S": S}, P=P)
        stats = stats_of_db(db, heavy_hitters=8)
        plain = plan_par([q])
        # skew_factor=1.0: annotate as soon as a key crosses the fair
        # share — the ladder gates the *leveling mechanism*, so the rung
        # where Zipf(1.0) sits just under the default 2x bar must defend
        # too, not dodge the gate by staying unannotated
        defended = annotate_skew(plain, stats, P, packing=False, skew_factor=1.0)
        rows_ref = None
        for variant, plan, on in (("undefended", plain, False),
                                  ("defended", defended, True)):
            cfg = ExecutorConfig(
                packing=False, probe_backend="sorted", skew_defense=on
            )
            Executor(dict(db), SimComm(P), cfg).execute(plan)  # warm
            ex = Executor(dict(db), SimComm(P), cfg)
            env, report = ex.execute(plan)
            rows = _rows_of(env, "zout")
            if rows_ref is None:
                rows_ref = rows
            sm = report.summary()
            ann = [j.skew for r in plan.rounds for j in r.jobs
                   if isinstance(j, MSJJob) and j.skew is not None]
            out.append({
                "exponent": s,
                "variant": variant,
                "bytes_shuffled": int(sm["bytes_shuffled"]),
                "forward_cap": max(  # MSJ's forward shuffle, not EVAL's
                    (r.stats.get("forward_cap", 0) for r in report.records
                     if not isinstance(r.job, EvalJob)),
                    default=0,
                ),
                "R": max((a.R for a in ann), default=0) if on else 0,
                "hot_keys": sum(len(a.hot) for a in ann) if on else 0,
                "replicated": sum(
                    r.stats.get("replicated", 0) for r in report.records
                ),
                "net_s": float(report.net_time),
                "total_s": float(report.total_time),
                "bit_identical": rows == rows_ref,
            })
    return out


def acceptance(rows: list[dict]) -> dict:
    """The deterministic acceptance block committed with the ladder."""
    defended = {r["exponent"]: r for r in rows if r["variant"] == "defended"}
    base_cap = defended[EXPONENTS[0]]["forward_cap"]
    return {
        "zipf_bit_identical": all(r["bit_identical"] for r in rows),
        "zipf_flat": all(
            r["forward_cap"] <= base_cap * FLAT_TOL for r in defended.values()
        ),
        "zipf_defended_max_cap": max(r["forward_cap"] for r in defended.values()),
        "zipf_uniform_cap": base_cap,
    }
