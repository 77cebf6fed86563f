"""Spans on the profiler's clock, and phase-span trees for the executor's
event timeline (DESIGN.md §14).

:func:`annotate` is the one span primitive: a
``jax.profiler.TraceAnnotation``, always on.  With no profiler session
open it costs about a microsecond and records nothing; under
``jax.profiler.start_trace`` it lands in the trace's host plane on the
same clock as the device's operations, which is what lets a reader say
what the host was doing while the device sat idle and which phase
launched each executable.  The service, the executor and every MSJ/EVAL
pipeline stage run under such spans whether or not a :class:`Tracer` is
held; :func:`host_read` puts every blocking device→host read under a
``host.sync`` span of its own.

A :class:`Span` is one named interval *inside* a job attempt — a pipeline
stage (``msj.shuffle.fwd``, ``msj.probe``), a retry attempt
(``ft.attempt``), or host-side bookkeeping (``ft.taint.sweep``) — with
wall seconds, free-form args (bytes, rows, outcome), and child spans.
Span times are stored **relative to the enclosing job's dispatch** so the
exporter can place them under the job slice at any virtual timeline
position, and they are rescaled whenever the executor rescales the job's
wall (``wall_scale`` straggler injection, speculation-loser truncation),
keeping every child interval inside its parent.

The contract with the hot path: ``Span`` trees exist only when a
:class:`Tracer` is held and enabled (:func:`phase` checks), and no span,
profiler annotation or ``Span`` alike, syncs the device or changes what
is dispatched — outputs are bit-identical with ``tracer=None``, with a
tracer, and under the profiler.
"""
from __future__ import annotations

import time
from contextlib import contextmanager
from dataclasses import dataclass, field

import jax
import numpy as np
from jax.profiler import TraceAnnotation


def annotate(name: str, **args) -> TraceAnnotation:
    """A span named ``name`` on the profiler's clock, with ``args`` as its
    stats; use as a context manager.  Always active, and nearly free with
    no profiler session open.  ``set_metadata(**more)`` on the entered
    span adds stats known only at its end."""
    return TraceAnnotation(name, **args)


def host_read(x, site: str) -> np.ndarray:
    """Read a device value to the host under a ``host.sync`` span naming
    its ``site``: the one door of every blocking device→host read on the
    served path, so a trace counts them and charges the idle time they
    cause to them.  A value already on the host passes through with no
    span."""
    if not isinstance(x, jax.Array):
        return np.asarray(x)
    with annotate("host.sync", site=site):
        return np.asarray(x)


@contextmanager
def phase(tracer, name: str, cat: str = "phase", **args):
    """Run a phase under a profiler span and, when ``tracer`` is held and
    enabled, under a recorded :class:`Span` too.  Yields the ``Span``, or
    None when there is no tracer to record it."""
    if tracer is not None and tracer.enabled:
        with tracer.span(name, cat, **args) as sp:
            yield sp
    else:
        with annotate(name, **args):
            yield None


@dataclass
class Span:
    """One traced phase: ``[t0, t0 + dur)`` relative to the job dispatch."""

    name: str
    cat: str = "phase"
    t0: float = 0.0
    dur: float = 0.0
    args: dict = field(default_factory=dict)
    children: list["Span"] = field(default_factory=list)

    def walk(self):
        yield self
        for c in self.children:
            yield from c.walk()


def rebase(spans: list[Span], t0: float, scale: float = 1.0) -> list[Span]:
    """Rebase absolute perf_counter times to offsets from ``t0`` and scale
    every interval by ``scale`` — the executor applies the same factor it
    applied to the job's wall (straggler injection / loser truncation), so
    spans stay nested inside the job slice.  Children share the parent's
    origin (all offsets are job-relative, not parent-relative)."""
    for sp in spans:
        sp.t0 = (sp.t0 - t0) * scale
        sp.dur *= scale
        rebase(sp.children, t0, scale)
    return spans


def scale_spans(spans: list[Span], scale: float) -> list[Span]:
    """Rescale already-rebased spans (speculation-loser truncation)."""
    for sp in spans:
        sp.t0 *= scale
        sp.dur *= scale
        scale_spans(sp.children, scale)
    return spans


class Tracer:
    """Collects nested spans via a context-manager stack.

    ``capture()`` opens a fresh collection root (one per job attempt in
    the executor) and yields the list spans land in; ``span(name)`` times
    a phase, nests it under the innermost open span, and annotates it on
    the profiler's clock.  A tracer is reusable and single-threaded — the
    executor dispatches jobs serially on this container, so one stack
    suffices.
    """

    def __init__(self, enabled: bool = True):
        self.enabled = enabled
        self._stack: list[list[Span]] = []

    @contextmanager
    def capture(self):
        """Collect top-level spans of one job attempt into a fresh list.

        Span ``t0`` values are raw ``perf_counter`` readings until the
        caller runs :func:`rebase` against the attempt's dispatch time.
        """
        root: list[Span] = []
        self._stack.append(root)
        try:
            yield root
        finally:
            self._stack.pop()

    @contextmanager
    def span(self, name: str, cat: str = "phase", **args):
        """Time one phase; nests under the innermost open span (if any).

        Yields the :class:`Span` so callers can attach result args
        (bytes, rows, outcome) after the timed region.
        """
        sp = Span(name, cat, time.perf_counter(), 0.0, dict(args))
        if not self._stack:
            self._stack.append([])  # tolerate spans outside capture()
        self._stack[-1].append(sp)
        self._stack.append(sp.children)
        try:
            with annotate(name, **args):
                yield sp
        finally:
            self._stack.pop()
            sp.dur = time.perf_counter() - sp.t0
