"""Elastic rescaling: move a run to a different mesh shape.

Two pieces:

* **Model state** — :func:`reshard_state` re-puts every leaf under the
  new mesh's NamedSharding (checkpoint.load already does this from disk;
  this is the in-memory path for live rescale).
* **Engine relations** — :func:`repartition_relation` re-partitions an
  SGF relation's rows over a new shard count (P changes with cluster
  size); row placement is hash/block-based so results are identical.
* **Shard loss + lineage recovery** (DESIGN.md §13) —
  :func:`lose_shard` simulates losing one partition of an in-memory
  relation (what a :class:`repro.core.executor.ShardLoss` injector does
  before raising); :func:`recover_shard` re-materializes that partition
  bit-identically from a durable lineage source (the catalog's
  host-resident rows in the service).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from repro.core.relation import Relation
from repro.obs.tracer import host_read


def reshard_state(state, specs, new_mesh):
    def put(leaf, spec):
        return jax.device_put(np.asarray(leaf), NamedSharding(new_mesh, spec))

    return jax.tree.map(put, state, specs)


def repartition_relation(rel: Relation, new_P: int, *, partition: str = "block") -> Relation:
    # Emit rows in round-robin insertion order — (P, cap) transposed to
    # (cap, P) — the inverse of from_numpy's block fill.  A pristine
    # block-partitioned relation therefore repartitions to the *canonical*
    # placement at the new P (same rows land on the same shards as a fresh
    # from_numpy build), which shard-loss lineage recovery relies on.
    rows = host_read(rel.data, "elastic.repartition")
    rows = rows.transpose(1, 0, 2).reshape(-1, rel.arity)
    valid = host_read(rel.valid, "elastic.repartition").transpose(1, 0).reshape(-1)
    return Relation.from_numpy(rel.name, rows[valid], P=new_P, partition=partition)


def repartition_db(db: dict, new_P: int) -> dict:
    return {name: repartition_relation(r, new_P) for name, r in db.items()}


def lose_shard(rel: Relation, shard: int) -> Relation:
    """Simulate losing partition ``shard``: its rows are zeroed and its
    validity mask cleared, exactly what a dead reducer leaves behind in
    cluster memory.  The relation stays well-formed (the engine computes
    on it without error — just silently wrong), which is why
    :class:`~repro.core.executor.ShardLoss` must be *raised* alongside."""
    if not 0 <= shard < rel.P:
        raise ValueError(f"shard {shard} out of range for P={rel.P}")
    return Relation(
        rel.name, rel.data.at[shard].set(0), rel.valid.at[shard].set(False)
    )


def recover_shard(
    damaged: Relation, source: Relation, shard: int, *, partition: str = "block"
) -> Relation:
    """Re-materialize partition ``shard`` of ``damaged`` from the durable
    ``source`` (MapReduce lineage: re-run the map split, not the job).

    When ``source`` is resident at the same P and cap, the shard is
    spliced back verbatim — bit-identical to the pre-loss copy, gaps in
    the validity mask included.  A source at a different shape (the
    elastic case: lineage kept at old P after a rescale) is first
    re-partitioned to ``damaged.P`` and its valid rows front-packed into
    the shard, which preserves row *content* but not slot layout."""
    if damaged.arity != source.arity:
        raise ValueError(
            f"arity mismatch: damaged {damaged.arity} vs lineage {source.arity}"
        )
    if not 0 <= shard < damaged.P:
        raise ValueError(f"shard {shard} out of range for P={damaged.P}")
    if source.P != damaged.P:
        source = repartition_relation(source, damaged.P, partition=partition)
    if source.cap == damaged.cap:
        sdata, svalid = source.data[shard], source.valid[shard]
    else:
        rows = host_read(source.data[shard], "elastic.recover")
        valid = host_read(source.valid[shard], "elastic.recover").reshape(-1)
        packed = rows[valid]
        if len(packed) > damaged.cap:
            raise ValueError(
                f"recovered shard load {len(packed)} overflows capacity "
                f"{damaged.cap} of {damaged.name!r}"
            )
        data = np.zeros((damaged.cap, damaged.arity), np.int32)
        vmask = np.zeros((damaged.cap,), bool)
        data[: len(packed)] = packed
        vmask[: len(packed)] = True
        sdata, svalid = jnp.asarray(data), jnp.asarray(vmask)
    return Relation(
        damaged.name,
        damaged.data.at[shard].set(sdata),
        damaged.valid.at[shard].set(svalid),
    )
