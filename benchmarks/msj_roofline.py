"""Roofline/§Perf benchmark for the paper's own technique: one MSJ job
run on SimComm (P shards stacked on one device), with the paper's
optimizations toggled — (packing, bloom, fused 1-ROUND) — plus the
engine-side ladder on top: fingerprint message layout, two-phase
count-sized shuffle, and the bucketed probe backend.  Reports exact
shuffled bytes (the collective-term driver) and wall-clock per variant.

This is the "most representative of the paper" hillclimb cell: the
optimization sequence IS the paper's §5.1 list plus the beyond-paper
generalized 1-ROUND and bloom prefilter (DESIGN.md §7), continued by the
hot-path work of DESIGN.md §5–§6.  The ``seed:*`` variants pin the
pre-fingerprint configuration (legacy message layout, worst-case forward
capacity, sort-merge probe) so the reduction is measured against the seed
``probe_sorted`` path, not a moving target.

``run`` returns structured dicts (machine-readable via
``benchmarks.run --json``); ``kernel_bench`` micro-benchmarks the probe
backends outside the vmapped pipeline, where the bucketed kernel's
tile-skip predicate is a real branch.
"""
from __future__ import annotations

import time
from dataclasses import dataclass

import jax

from repro.core import queries as Q
from repro.core.executor import Executor, ExecutorConfig
from repro.core.planner import EvalJob, plan_one_round, plan_par, plan_greedy
from repro.core.costmodel import HADOOP, stats_of_db
from repro.core.relation import db_from_dict
from repro.engine.comm import SimComm


@dataclass
class Variant:
    name: str
    packing: bool
    bloom_bits: int
    strategy: str  # par | greedy | one_round
    fingerprint: bool = True
    count_sized: bool = True
    probe_backend: str = "auto"


#: The ladder.  ``seed:*`` rungs reproduce the seed configuration exactly
#: (legacy layout, worst-case cap, sorted probe); the last three rungs add
#: this PR's hot-path work one lever at a time.
VARIANTS = [
    Variant("seed:baseline(no-pack,PAR)", False, 0, "par", False, False, "sorted"),
    Variant("seed:+packing", True, 0, "par", False, False, "sorted"),
    Variant("seed:+greedy-grouping", True, 0, "greedy", False, False, "sorted"),
    Variant("seed:+bloom", True, 8192, "greedy", False, False, "sorted"),
    Variant("seed:+fused-1ROUND", True, 8192, "one_round", False, False, "sorted"),
    Variant("+fingerprint", True, 8192, "one_round", True, False, "sorted"),
    Variant("+count-sized-shuffle", True, 8192, "one_round", True, True, "sorted"),
    Variant("+bucketed-probe(auto)", True, 8192, "one_round", True, True, "auto"),
]


def run(n_guard: int = 8192, sel: float = 0.3, P: int = 16) -> list[dict]:
    """Execute the ladder on the A3 query family; one dict per variant."""
    qs = Q.make_queries("A3")
    db_np = Q.gen_db(qs, n_guard=n_guard, n_cond=n_guard, sel=sel)
    db = db_from_dict(db_np, P=P)
    out: list[dict] = []
    for v in VARIANTS:
        if v.strategy == "par":
            plan = plan_par(qs)
        elif v.strategy == "greedy":
            plan = plan_greedy(qs, stats_of_db(db), HADOOP)
        else:
            plan = plan_one_round(qs)
        cfgx = ExecutorConfig(
            packing=v.packing,
            bloom_bits=v.bloom_bits,
            fingerprint=v.fingerprint,
            count_sized=v.count_sized,
            probe_backend=v.probe_backend,
        )
        # warm run (jit/trace caches), then measured run — common.py idiom,
        # so every rung is compared warm rather than charging compile time
        # to whichever variant traced a shape first
        Executor(dict(db), SimComm(P), cfgx).execute(plan)
        ex = Executor(dict(db), SimComm(P), cfgx)
        env, report = ex.execute(plan)
        s = report.summary()
        out.append(
            {
                "variant": v.name,
                "bytes_shuffled": int(s["bytes_shuffled"]),
                "input_rows": int(s["input_rows"]),
                "jobs": int(s["jobs"]),
                "net_s": float(report.net_time),
                "total_s": float(report.total_time),
                "forward_cap": max(  # MSJ's forward shuffle, not EVAL's
                    (r.stats.get("forward_cap", 0) for r in report.records
                     if not isinstance(r.job, EvalJob)),
                    default=0,
                ),
            }
        )
    return out


def kernel_bench(n: int = 4096, kw: int = 2, repeats: int = 3) -> list[dict]:
    """Probe-backend microbenchmark at reducer-realistic sizes, unvmapped.

    Inside the SimComm pipeline every backend runs under vmap; here the
    kernels run standalone, so the bucketed kernel's range predicate
    actually skips non-overlapping tile pairs (as it does compiled on TPU).
    """
    import numpy as np
    import jax.numpy as jnp

    from repro.core.msj import probe_sorted
    from repro.kernels.msj_probe import ops as pops

    rng = np.random.default_rng(0)
    bs = jnp.asarray(rng.integers(0, 4, n), jnp.int32)
    bk = jnp.asarray(rng.integers(0, 50_000, (n, kw)), jnp.int32)
    ps = jnp.asarray(rng.integers(0, 4, n), jnp.int32)
    pk = jnp.asarray(rng.integers(0, 50_000, (n, kw)), jnp.int32)
    ones = jnp.ones(n, bool)

    backends = {
        "sorted(jnp)": lambda: probe_sorted(bs, bk, ones, ps, pk, ones),
        "pallas-unbucketed": lambda: pops.probe(bs, bk, ones, ps, pk, ones),
        "pallas-bucketed": lambda: pops.probe_bucketed(bs, bk, ones, ps, pk, ones),
    }
    out: list[dict] = []
    want = None
    for name, f in backends.items():
        r = jax.block_until_ready(f())  # warmup / compile
        t0 = time.perf_counter()
        for _ in range(repeats):
            r = jax.block_until_ready(f())
        ms = (time.perf_counter() - t0) / repeats * 1e3
        if want is None:
            want = np.asarray(r)
        else:
            np.testing.assert_array_equal(np.asarray(r), want)
        out.append({"backend": name, "n": n, "kw": kw, "ms": round(ms, 2)})
    return out
