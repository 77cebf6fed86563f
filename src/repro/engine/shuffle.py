"""Radix partition for the shuffle phase + the heavy-hitter sketch.

``partition`` turns a shard-local message buffer into a ``(P, cap, W)``
send buffer addressed by destination shard, with exact overflow accounting.
The exchange itself (``all_to_all``) is performed by the comm runner.

``topk_fp_counts`` / ``merge_topk`` are the bounded top-k sketch behind
the skew defense (DESIGN.md §17): per-shard value counts are exact (one
stable sort + run-length encoding, the same primitive the packing dedup
uses), and only the *merge* across shards is bounded to k entries — a
value missing from every shard's local top-k cannot surface globally,
which is the sketch's only error mode.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.obs.tracer import host_read


def partition(
    msgs: jnp.ndarray,  # (N, W) int32
    valid: jnp.ndarray,  # (N,) bool
    dest: jnp.ndarray,  # (N,) int32 in [0, P)
    P: int,
    cap: int,
):
    """Route messages into per-destination buckets.

    Returns ``(buf (P, cap, W) int32, bufvalid (P, cap) bool,
    overflow (scalar int32), counts (P,) int32)``.

    Deterministic: a stable sort by destination preserves source order
    within each bucket (reproducible runs — required for checkpoint/restart
    equivalence tests).
    """
    N, W = msgs.shape
    d = jnp.where(valid, dest, P).astype(jnp.int32)  # invalid -> sentinel bucket
    order = jnp.argsort(d, stable=True)
    d_s = d[order]
    msgs_s = msgs[order]
    counts = jnp.bincount(d_s, length=P + 1)
    offsets = jnp.concatenate([jnp.zeros((1,), counts.dtype), jnp.cumsum(counts)[:-1]])
    pos = jnp.arange(N, dtype=jnp.int32) - offsets[d_s].astype(jnp.int32)
    buf = jnp.zeros((P, cap, W), jnp.int32)
    buf = buf.at[d_s, pos].set(msgs_s, mode="drop")
    bufvalid = jnp.zeros((P, cap), bool)
    inrange = (d_s < P) & (pos < cap)
    bufvalid = bufvalid.at[d_s, pos].set(inrange, mode="drop")
    overflow = jnp.maximum(counts[:P] - cap, 0).sum().astype(jnp.int32)
    return buf, bufvalid, overflow, counts[:P].astype(jnp.int32)


def flatten_recv(buf: jnp.ndarray, bufvalid: jnp.ndarray):
    """(P, cap, W) received buckets -> (P*cap, W) flat rows + validity."""
    P, cap, W = buf.shape
    return buf.reshape(P * cap, W), bufvalid.reshape(P * cap)


def topk_fp_counts(vals: jnp.ndarray, valid: jnp.ndarray, k: int):
    """Per-shard top-k value counts: ``(N,) int32 values, (N,) bool`` ->
    ``((k,) int32 values, (k,) int32 counts)``, counts descending.

    Counts are exact within the shard (sort + run-length encode); only
    the k-truncation loses information.  Slots past the number of
    distinct valid values carry count 0 — callers must treat count-0
    entries as absent rather than as "value 0 seen zero times".
    """
    n = int(vals.shape[0])
    k = max(1, min(int(k), n))
    # invalid rows sort to the end (uint32 max sentinel); a *valid* row
    # that happens to hold 0xFFFFFFFF still counts correctly because run
    # boundaries also break on validity, and leads are masked to valid
    sortkey = jnp.where(valid, vals.astype(jnp.uint32), jnp.uint32(0xFFFFFFFF))
    order = jnp.argsort(sortkey, stable=True)
    v_s = vals[order]
    ok_s = valid[order]
    lead = jnp.ones((n,), bool)
    if n > 1:
        lead = lead.at[1:].set((v_s[1:] != v_s[:-1]) | ~ok_s[:-1])
    lead = lead & ok_s
    run = jnp.cumsum(lead.astype(jnp.int32)) - 1  # run id per sorted row
    ridx = jnp.where(ok_s, run, n)  # invalid rows -> dropped
    counts = jnp.zeros((n,), jnp.int32).at[ridx].add(
        jnp.ones((n,), jnp.int32), mode="drop"
    )
    rvals = jnp.zeros((n,), jnp.int32).at[jnp.where(lead, run, n)].set(
        v_s, mode="drop"
    )
    top = jnp.argsort(-counts, stable=True)[:k]
    return rvals[top], counts[top]


def merge_topk(vals, counts, k: int):
    """Host-side merge of per-shard sketches into one global top-k.

    ``vals``/``counts`` are ``(P, k)`` (or any leading shape) arrays from
    :func:`topk_fp_counts`.  Returns ``((value, count), ...)`` sorted by
    count descending then value, at most ``k`` entries, count-0 slots
    dropped.  A value absent from *every* shard's local top-k cannot
    appear — that is the sketch's only recall loss, bounded by the
    per-shard k (tests/test_skew.py pins the recall floor).
    """
    v = host_read(vals, "shuffle.merge_topk").reshape(-1)
    c = host_read(counts, "shuffle.merge_topk").reshape(-1)
    totals: dict[int, int] = {}
    for value, count in zip(v.tolist(), c.tolist()):
        if count > 0:
            totals[int(value)] = totals.get(int(value), 0) + int(count)
    ranked = sorted(totals.items(), key=lambda vc: (-vc[1], vc[0]))
    return tuple(ranked[: max(0, int(k))])
