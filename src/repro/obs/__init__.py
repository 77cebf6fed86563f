"""Observability: spans on the profiler's clock, phase-span tracing, the
metric registry, and the Chrome/Perfetto trace exporter (DESIGN.md §14).

* :mod:`repro.obs.tracer` — :func:`annotate`, the always-on span on the
  profiler's clock that every service, executor and pipeline phase runs
  under; :func:`host_read`, the one door of device→host reads, each
  under a ``host.sync`` span; and :class:`Span`/:class:`Tracer`: nested
  phase spans (count-exchange, forward shuffle, probe, scatter, retry
  attempts, taint sweeps) hanging off each
  :class:`~repro.core.executor.JobRecord`, recorded only when a tracer is
  held.  Outputs are bit-identical either way.
* :mod:`repro.obs.metrics` — counters / gauges / HDR-style histograms in
  one ``msj.* / svc.* / ft.*`` namespace, absorbing the service, cache,
  and fault-tolerance counters, plus a JSONL sink.
* :mod:`repro.obs.perfetto` — ``trace_event`` JSON writer (one track per
  cluster slot, flow arrows for DAG edges / speculation / taint), a
  schema validator, and :func:`~repro.obs.perfetto.report_from_trace`,
  which reconstructs a Report whose ``net_time_by_events`` replays
  bit-exactly from the exported spans alone.
"""
from repro.obs.metrics import (  # noqa: F401
    Counter,
    Gauge,
    Histogram,
    JsonlSink,
    MetricRegistry,
    counter_attr,
)
from repro.obs.perfetto import (  # noqa: F401
    audit_trace,
    phase_breakdown,
    report_from_trace,
    trace_events,
    validate_trace,
    write_trace,
)
from repro.obs.tracer import Span, Tracer, annotate, host_read, phase  # noqa: F401
