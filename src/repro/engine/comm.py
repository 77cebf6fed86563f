"""Communication runners: the same per-shard stage functions execute either

* **SimComm** — stacked ``(P, ...)`` arrays on however many real devices are
  available; per-shard stages run under ``jax.vmap`` and ``all_to_all`` is a
  leading-axes transpose. This is bit-identical to the device path and lets
  CPU tests/benches use any shard count.
* **MeshComm** — one shard per device via ``shard_map`` over a mesh axis;
  ``all_to_all`` is ``jax.lax.all_to_all`` over the ICI. Nothing on the
  served path uses it yet: the service and the benchmarks run SimComm,
  which on a TPU stacks all P shards on one chip.

Stage functions are written against shard-local views and a ``shard_id``
scalar; the runner stitches them together. This mirrors production engines
(e.g. comm abstraction layers in DeepSpeed/Pathways) and keeps the paper's
map / shuffle / reduce structure explicit.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.obs.tracer import phase


@dataclass(frozen=True)
class SimComm:
    """Stacked-array simulation of a P-shard mesh."""

    P: int

    def shard_ids(self) -> jnp.ndarray:
        return jnp.arange(self.P, dtype=jnp.int32)

    def vmap(self, fn: Callable) -> Callable:
        return jax.vmap(fn)

    def all_to_all(self, x: jnp.ndarray) -> jnp.ndarray:
        """x: (P, P, ...) stacked [src, dest, ...] -> [dest, src, ...]."""
        return jnp.swapaxes(x, 0, 1)

    def all_gather(self, x: jnp.ndarray) -> jnp.ndarray:
        """x: (P, ...) per-shard -> (P, P, ...) replicated gather."""
        return jnp.broadcast_to(x[None], (self.P,) + x.shape)

    def all_reduce_sum(self, x: jnp.ndarray) -> jnp.ndarray:
        """x: (P, ...) -> (P, ...) each shard holding the global sum."""
        s = x.sum(axis=0)
        return jnp.broadcast_to(s[None], x.shape)

    def all_reduce_or(self, x: jnp.ndarray) -> jnp.ndarray:
        s = x.any(axis=0) if x.dtype == jnp.bool_ else x.max(axis=0)
        return jnp.broadcast_to(s[None], x.shape)


@dataclass(frozen=True)
class MeshComm:
    """Device-backed comm over one (possibly flattened) mesh axis."""

    mesh: Mesh
    axis: str | tuple[str, ...]

    @property
    def P(self) -> int:
        axes = (self.axis,) if isinstance(self.axis, str) else self.axis
        n = 1
        for a in axes:
            n *= self.mesh.shape[a]
        return n

    @property
    def axis_name(self):
        return self.axis

    def shard_id(self) -> jnp.ndarray:
        axes = (self.axis,) if isinstance(self.axis, str) else self.axis
        idx = jnp.int32(0)
        for a in axes:
            idx = idx * self.mesh.shape[a] + jax.lax.axis_index(a)
        return idx

    def all_to_all(self, x: jnp.ndarray) -> jnp.ndarray:
        """x local: (P, ...) send row j to shard j; receive likewise."""
        return jax.lax.all_to_all(x, self.axis, split_axis=0, concat_axis=0, tiled=False)

    def all_gather(self, x: jnp.ndarray) -> jnp.ndarray:
        return jax.lax.all_gather(x, self.axis)

    def all_reduce_sum(self, x: jnp.ndarray) -> jnp.ndarray:
        return jax.lax.psum(x, self.axis)

    def all_reduce_or(self, x: jnp.ndarray) -> jnp.ndarray:
        if x.dtype == jnp.bool_:
            return jax.lax.pmax(x.astype(jnp.int32), self.axis).astype(bool)
        return jax.lax.pmax(x, self.axis)


Comm = SimComm | MeshComm


def run_pipeline(
    comm: Comm,
    stages: Sequence[Callable],
    stacked_args,
    *,
    tracer=None,
    names: Sequence[str] | None = None,
):
    """Run ``stages`` alternating per-shard compute with all_to_all.

    Each stage has signature ``stage(shard_id, carry) -> (send, carry)`` where
    ``send`` is either None (no shuffle after this stage) or a pytree of
    ``(P, ...)`` buffers to exchange; the exchanged buffers are passed as
    ``carry`` input (tuple ``(recv, carry)``) to the next stage.

    For SimComm, ``stacked_args`` carries a leading P axis; for MeshComm the
    caller is expected to invoke this inside ``shard_map`` (see
    :func:`mesh_pipeline`).

    Every SimComm stage runs under a profiler span (``repro.obs.phase``,
    DESIGN.md §14) named by ``names``, falling back to the stage
    function's name, so a trace attributes the executables a stage
    launches to it; ``tracer`` (a :class:`repro.obs.Tracer`) additionally
    records each stage as a phase ``Span``.  Neither syncs the carry
    between stages: the dispatch stream, shuffle/compute overlap
    (DESIGN.md §16) and the output bits are those of a run without
    either.  MeshComm runs inside ``shard_map``, where a host span would
    time the trace, not the run, so it takes no spans.
    """
    if isinstance(comm, SimComm):
        carry = stacked_args
        for i, stage in enumerate(stages):
            label = names[i] if names and i < len(names) else getattr(
                stage, "__name__", f"stage{i}"
            )
            with phase(tracer, label):
                send, carry = jax.vmap(stage)(comm.shard_ids(), carry)
                if send is not None:
                    recv = jax.tree.map(comm.all_to_all, send)
                    carry = (recv, carry)
        return carry
    else:
        sid = comm.shard_id()
        carry = stacked_args
        for stage in stages:
            send, carry = stage(sid, carry)
            if send is not None:
                recv = jax.tree.map(comm.all_to_all, send)
                carry = (recv, carry)
        return carry


def mesh_pipeline(mesh: Mesh, axis, stages, in_specs, out_specs):
    """Wrap :func:`run_pipeline` in a shard_map over ``axis``."""
    comm = MeshComm(mesh, axis)

    def body(*stacked_args):
        return run_pipeline(comm, stages, stacked_args if len(stacked_args) != 1 else stacked_args[0])

    return jax.shard_map(body, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False)
