"""The EVAL operator — one job evaluating Boolean combinations
``Z_u := X0_u ∧ φ_u(X1_u ... Xn_u)`` (paper Section 4.3).

Every row of every input relation is routed by a hash of its *tuple*
(one all_to_all, its buckets sized from the rows each shard sends each
destination); on the receiving shard rows are grouped by
``(unit, tuple)`` with a single lexicographic sort, each group's membership
bitmask is formed with a segment-OR, and the Boolean formula is applied to
the bitmask — exactly the paper's reducer, vectorized.

Multiple EVAL units (one per BSGF query of a stratum) share the job, which
is how the planner amortizes job overhead across the queries of one level.
Output relations are distinct-tuple sets (the reducer groups by tuple).
"""
from __future__ import annotations

import zlib
from dataclasses import dataclass
from typing import Sequence

import jax
import jax.numpy as jnp

from repro.core.algebra import Atom, Cond, eval_cond
from repro.core.msj import _lex_order
from repro.core.relation import Relation
from repro.engine import hashing, shuffle
from repro.engine.comm import Comm, run_pipeline
from repro.obs.tracer import host_read, phase


@dataclass(frozen=True)
class EvalUnit:
    """``name := π_{out_pos}(x0 ∧ cond)`` where cond's atoms map to the xs.

    ``out_pos`` (optional) projects the output onto a subset of the x0
    tuple's columns *after* the Boolean combination — required for
    soundness under negation when the query's SELECT list drops guard
    variables (see planner.py module docstring).
    """

    name: str
    x0: str  # relation name of the guard-projection input
    xs: tuple[str, ...]  # relation names of X_1..X_n (atom order)
    atoms: tuple[Atom, ...]  # conditional atoms, aligned with xs
    cond: Cond | None
    out_pos: tuple[int, ...] | None = None
    #: shuffle-placement salt; ``None`` falls back to a hash of ``name``
    salt: int | None = None


def _unit_salt(name: str) -> int:
    """Shuffle salt for an EVAL unit, derived from its *name* rather than
    its position in the job: a unit's output placement must not change when
    failure isolation narrows the job around it (DESIGN.md §13)."""
    return zlib.crc32(name.encode()) & 0x7FFFFFFF


def query_salt(q) -> int:
    """Placement salt from a BSGF query's *structure* — not its name, which
    in the service is canonical and batch-positional (``q0, q1, ...``).
    The same query must land its output rows on the same shards no matter
    which co-batched queries it is fused with (and no matter how failure
    isolation narrows the job), or survivor outputs would not be
    bit-identical across batch compositions (DESIGN.md §13)."""
    key = repr((q.out_vars, q.guard, q.atoms, q.cond))
    return zlib.crc32(key.encode()) & 0x7FFFFFFF


def run_eval(
    env: dict[str, Relation],
    units: Sequence[EvalUnit],
    comm: Comm,
    *,
    forward_cap: int | None = None,
    count_sized: bool = True,
    tracer=None,
):
    """Execute one EVAL job. Returns ``({name: Relation}, stats)``.

    The forward shuffle is count-sized, as MSJ's is (DESIGN.md §6): the
    map stage also counts each shard's valid rows per destination, the
    largest (source, destination) count is read once (``eval.count_cap``)
    and rounded up to a power of two, and the partition and the reduce run
    at that bucket capacity.  ``forward_cap`` overrides the count (the
    executor's overflow ladder); ``count_sized=False``, or counts that are
    traced values (MeshComm inside ``shard_map``), fall back to the
    worst-case bound, the sum of the inputs' capacities.

    The two pipeline phases run under profiler spans (``eval.shuffle`` —
    tuple routing, the count read and the exchange — and ``eval.reduce``
    — sorted grouping + formula evaluation), which ``tracer`` also
    records as phase spans (DESIGN.md §14); outputs are the same with or
    without it.
    """
    P = comm.P
    units = tuple(units)
    max_members = max(1 + len(u.xs) for u in units)
    arities = []
    for u in units:
        a = env[u.x0].arity
        for x in u.xs:
            if env[x].arity != a:
                raise ValueError(f"arity mismatch in EVAL unit {u.name}")
        arities.append(a)
    A = max(arities)

    inputs: list[tuple[int, int, str]] = []  # (unit, member, relname)
    for ui, u in enumerate(units):
        inputs.append((ui, 0, u.x0))
        for mi, x in enumerate(u.xs):
            inputs.append((ui, mi + 1, x))
    rel_names = sorted({name for _, _, name in inputs})

    bound = max(1, sum(env[name].cap for _, _, name in inputs))
    W = A + 2  # [unit, member, tuple cols...]

    def stage_map(sid, local_db):
        msgs, valid, dest = [], [], []
        for ui, mi, name in inputs:
            rel = local_db[name]
            tup = rel.data
            if rel.arity < A:
                tup = jnp.concatenate(
                    [tup, jnp.zeros((rel.cap, A - rel.arity), jnp.int32)], axis=1
                )
            u = units[ui]
            salt = u.salt if u.salt is not None else _unit_salt(u.name)
            h = hashing.hash_cols(tup[:, : arities[ui]], salt=salt)
            msgs.append(
                jnp.concatenate(
                    [
                        jnp.full((rel.cap, 1), ui, jnp.int32),
                        jnp.full((rel.cap, 1), mi, jnp.int32),
                        tup,
                    ],
                    axis=1,
                )
            )
            valid.append(rel.valid)
            dest.append(hashing.bucket_of(h, P))
        msgs = jnp.concatenate(msgs, 0)
        valid = jnp.concatenate(valid, 0)
        dest = jnp.concatenate(dest, 0)
        counts = jnp.bincount(jnp.where(valid, dest, P), length=P + 1)[:P]
        return None, (msgs, valid, dest, counts.astype(jnp.int32))

    def stage_partition(sid, mapped):
        msgs, valid, dest, _ = mapped
        sent = valid.sum().astype(jnp.int32)
        buf, bufvalid, ovf, _ = shuffle.partition(msgs, valid, dest, P, cap_s)
        return (buf, bufvalid), (ovf, sent)

    def stage_reduce(sid, args):
        (recv, recv_valid), (ovf, sent) = args
        flat, ok = shuffle.flatten_recv(recv, recv_valid)
        n = flat.shape[0]
        unit = jnp.where(ok, flat[:, 0], jnp.int32(2**30))
        member = flat[:, 1]
        tup = flat[:, 2:]
        order = _lex_order([unit] + [tup[:, k] for k in range(A)])
        unit_s, mem_s, tup_s, ok_s = unit[order], member[order], tup[order], ok[order]
        new_grp = jnp.ones((n,), bool)
        if n > 1:
            diff = (unit_s[1:] != unit_s[:-1]) | (tup_s[1:] != tup_s[:-1]).any(axis=1)
            new_grp = jnp.concatenate([jnp.ones((1,), bool), diff])
        gid = jnp.cumsum(new_grp.astype(jnp.int32)) - 1
        onehot = (
            (mem_s[:, None] == jnp.arange(max_members, dtype=jnp.int32)[None, :])
            & ok_s[:, None]
        ).astype(jnp.int32)
        group_mask = jax.ops.segment_max(onehot, gid, num_segments=n)  # (n, M)
        row_mask = group_mask[gid].astype(bool)

        # distinct-output leader: the first member-0 row of each group.
        flag = ok_s & (mem_s == 0)
        csum = jnp.cumsum(flag.astype(jnp.int32))
        excl = csum - flag.astype(jnp.int32)
        pos = jnp.arange(n, dtype=jnp.int32)
        g_start = jax.ops.segment_min(pos, gid, num_segments=n)
        base = excl[g_start]  # member-0 rows seen before this group
        is_leader = flag & ((csum - 1 - base[gid]) == 0)

        outs = {}
        for ui, u in enumerate(units):
            leaf = {a: row_mask[:, mi + 1] for mi, a in enumerate(u.atoms)}
            formula_ok = (
                eval_cond(u.cond, leaf) if u.cond is not None else jnp.ones((n,), bool)
            )
            zok = is_leader & (unit_s == ui) & row_mask[:, 0] & formula_ok
            cols = (
                list(u.out_pos)
                if u.out_pos is not None
                else list(range(arities[ui]))
            )
            outs[u.name] = Relation(u.name, tup_s[:, cols], zok)
        stats = {
            "overflow": ovf,
            "sent_fwd": sent,
            "recv_fwd": ok.sum().astype(jnp.int32),
            "hits": jnp.int32(0),
        }
        return None, (outs, stats)

    stacked = {name: env[name] for name in rel_names}
    with phase(tracer, "eval.shuffle") as sp:
        mapped = run_pipeline(comm, [stage_map], stacked)
        cap_s, counted = _forward_cap(
            mapped[3], bound, forward_cap=forward_cap, count_sized=count_sized
        )
        if sp is not None:
            sp.args["cap"] = cap_s
        exchanged = run_pipeline(comm, [stage_partition], mapped)
    outputs, stats = run_pipeline(
        comm, [stage_reduce], exchanged, tracer=tracer, names=["eval.reduce"]
    )
    stats = {k: jnp.asarray(v).sum() for k, v in stats.items()}
    # the counts read to size the buckets are P int32 per shard, as in
    # MSJ's count phase; charged so count-sizing can't hide traffic
    stats["bytes_fwd"] = stats["sent_fwd"] * W * 4 + (P * P * 4 if counted else 0)
    stats["bytes_bwd"] = jnp.int32(0)
    stats["forward_cap"] = cap_s
    return outputs, stats


def _forward_cap(
    counts, bound: int, *, forward_cap: int | None, count_sized: bool
) -> tuple[int, bool]:
    """The per-destination bucket capacity of EVAL's forward shuffle and
    whether it was read from ``counts`` (the per-shard, per-destination
    valid-row counts): the override, else the largest count rounded up to
    a power of two (so data of nearby sizes share compiled shapes) and
    held to ``bound``, else ``bound`` when counting is off or the counts
    are traced values."""
    if forward_cap is not None:
        return forward_cap, False
    if not count_sized or isinstance(counts, jax.core.Tracer):
        return bound, False
    top = int(host_read(jnp.max(counts), "eval.count_cap"))
    return min(1 << max(top - 1, 0).bit_length(), bound), True
