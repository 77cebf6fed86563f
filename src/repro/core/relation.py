"""Fixed-capacity relation storage.

A :class:`Relation` holds facts as a dense ``(P, cap, arity)`` int32 array
plus a ``(P, cap)`` validity mask, where ``P`` is the number of row shards
(the engine's "reducer count"). ``P == 1`` is the local/unsharded case.

TPU adaptation: Hadoop relations are unbounded files; here every relation has
a static capacity and a validity mask, and *overflow is detected exactly*
(counts are computed with integer reductions) and surfaced to the fault
supervisor.
"""
from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Iterable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.engine import hashing
from repro.obs.tracer import host_read


@jax.tree_util.register_pytree_node_class
@dataclass(frozen=True)
class Relation:
    name: str
    data: jnp.ndarray  # (P, cap, arity) int32
    valid: jnp.ndarray  # (P, cap) bool

    # -- pytree plumbing (name is static) ---------------------------------
    def tree_flatten(self):
        return (self.data, self.valid), self.name

    @classmethod
    def tree_unflatten(cls, name, children):
        data, valid = children
        return cls(name, data, valid)

    # -- shape accessors ---------------------------------------------------
    # Shapes are read from the trailing dims so the same accessors work on
    # the stacked (P, cap, arity) form and on shard-local (cap, arity) views
    # inside vmap / shard_map bodies.
    @property
    def P(self) -> int:
        return self.data.shape[0] if self.data.ndim == 3 else 1

    @property
    def cap(self) -> int:
        return self.data.shape[-2]

    @property
    def arity(self) -> int:
        return self.data.shape[-1]

    def count(self) -> jnp.ndarray:
        return self.valid.sum()

    # -- construction ------------------------------------------------------
    @classmethod
    def from_numpy(
        cls,
        name: str,
        rows: np.ndarray,
        *,
        P: int = 1,
        cap: int | None = None,
        partition: str = "block",
    ) -> "Relation":
        """Build a sharded relation from an ``(n, arity)`` numpy array.

        ``partition='block'`` round-robins rows over shards; ``'hash'``
        routes by a hash of the full tuple (used to co-partition for EVAL).
        """
        rows = np.asarray(rows, dtype=np.int32)
        if rows.ndim == 1:
            rows = rows[:, None]
        n, arity = rows.shape
        if partition == "block":
            dest = np.arange(n) % P
        elif partition == "hash":
            h = host_read(hashing.hash_cols(jnp.asarray(rows)), "relation.from_numpy")
            dest = np.asarray(h) % P
        else:
            raise ValueError(partition)
        per = np.bincount(dest, minlength=P)
        if cap is None:
            cap = max(1, int(per.max()) if n else 1)
        if int(per.max() if n else 0) > cap:
            raise ValueError(f"capacity {cap} overflows shard load {per.max()}")
        # each row goes to the next free slot of its shard, in input order
        order = np.argsort(dest, kind="stable")
        start = np.concatenate([[0], np.cumsum(per)[:-1]])
        slot = np.empty(n, np.int64)
        slot[order] = np.arange(n) - start[dest[order]]
        data = np.zeros((P, cap, arity), np.int32)
        valid = np.zeros((P, cap), bool)
        data[dest, slot] = rows
        valid[dest, slot] = True
        return cls(name, jnp.asarray(data), jnp.asarray(valid))

    @classmethod
    def from_tuples(cls, name: str, tuples: Iterable[Sequence[int]], **kw) -> "Relation":
        rows = np.asarray([tuple(t) for t in tuples], dtype=np.int32)
        if rows.size == 0:
            rows = rows.reshape(0, 1)
        return cls.from_numpy(name, rows, **kw)

    @classmethod
    def empty(cls, name: str, arity: int, *, P: int = 1, cap: int = 1) -> "Relation":
        return cls(
            name,
            jnp.zeros((P, cap, arity), jnp.int32),
            jnp.zeros((P, cap), bool),
        )

    # -- conversion (host side; tests/debug) --------------------------------
    def to_set(self) -> set[tuple[int, ...]]:
        data = np.asarray(self.data).reshape(-1, self.arity)
        valid = np.asarray(self.valid).reshape(-1)
        return {tuple(int(v) for v in row) for row in data[valid]}

    def rename(self, name: str) -> "Relation":
        return replace(self, name=name)

    def with_mask(self, mask: jnp.ndarray, name: str | None = None) -> "Relation":
        """Restrict validity (e.g. materializing a semi-join result)."""
        return Relation(name or self.name, self.data, self.valid & mask)

    def local(self, p: int) -> "Relation":
        """Shard-local view (used inside shard_map bodies / vmap)."""
        return Relation(self.name, self.data[p], self.valid[p])

    def compacted(self, cap: int | None = None) -> "Relation":
        """Pack valid rows to the front of each shard and shrink capacity.

        The target capacity is host-chosen (executor jobs are separate
        dispatches, so the sync is free); rows never move across shards.
        Keeps intermediate relations from inflating downstream shuffle
        buffers (Hadoop's "data size reduced after each step", adapted).
        """
        import numpy as np

        data = self.data if self.data.ndim == 3 else self.data[None]
        valid = self.valid if self.valid.ndim == 2 else self.valid[None]
        if cap is None:
            per_shard = (
                int(host_read(valid.sum(axis=1), "relation.compacted").max())
                if valid.size
                else 0
            )
            cap = max(1, int(2 ** np.ceil(np.log2(max(per_shard, 1)))))
        order = jnp.argsort(~valid, axis=1, stable=True)[:, :cap]
        new_data = jnp.take_along_axis(data, order[:, :, None], axis=1)
        new_valid = jnp.take_along_axis(valid, order, axis=1)
        # Zero the tail beyond the packed rows: invalid slots otherwise carry
        # whatever the producing job left there, which would make otherwise
        # identical outputs differ bit-wise across job compositions
        # (failure-narrowed jobs must reproduce the fault-free arrays).
        new_data = jnp.where(new_valid[:, :, None], new_data, 0)
        return Relation(self.name, new_data, new_valid)


Database = dict  # name -> Relation


def db_from_dict(
    rels: dict[str, np.ndarray | list], *, P: int = 1, cap: int | None = None
) -> Database:
    out = {}
    for name, rows in rels.items():
        if isinstance(rows, np.ndarray):
            out[name] = Relation.from_numpy(name, rows, P=P, cap=cap)
        else:
            out[name] = Relation.from_tuples(name, rows, P=P, cap=cap)
    return out
