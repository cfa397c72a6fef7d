"""repro.telemetry -- the unified observability plane.

A low-overhead, host-side telemetry subsystem for the serving stack:

* ``Telemetry`` -- the registry: counters, gauges, log2-bucketed
  histograms, a span/trace API (``with span(tel, "resolve_wave", ...)``,
  a no-op when ``tel`` is None; each span is also a profiler annotation
  ``repro.<name>`` on the device trace's clock) and a JSON-lines event
  sink;
* ``EnergyLedger`` (``tel.ledger``) -- per-commit watts decomposed into
  Eq.(1) networking vs Eq.(2) processing, per tier / tenant / region,
  integrated to joules over a replay horizon;
* compile attribution -- ``tel.attach_traces()`` hooks
  ``solvers.count_traces`` so every fresh jit trace is recorded with its
  entry name and abstract shape fingerprint, and ``tel.report()``
  cross-checks the log against live ``TRACE_COUNTS`` (and the CFN108
  static bounds when given);
* exporters -- streaming JSONL, Prometheus text exposition
  (``tel.prometheus()``), and the ``python -m repro.telemetry report``
  CLI.

Threading: pass ``telemetry=`` to ``OnlineEmbedder`` / ``CFNSession`` /
``FederatedSession`` / ``EnergyAwareScheduler`` (default ``None`` keeps
every instrumented path a strict no-op -- bit-identical placements,
zero extra compiles).  See docs/OBSERVABILITY.md.
"""
from .ledger import EnergyLedger, tiers_of
from .registry import Histogram, Span, Telemetry, span
from .report import (EVENT_SCHEMA, load_events, render, summarize_events,
                     validate_events)

__all__ = [
    "Telemetry", "Span", "span", "Histogram", "EnergyLedger", "tiers_of",
    "EVENT_SCHEMA", "load_events", "validate_events", "summarize_events",
    "render",
]
