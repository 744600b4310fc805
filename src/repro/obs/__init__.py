"""Observability layer: metrics registry, deterministic span tracing, and
the structured dependability event log.

Three measured-event substrates, one design rule — *observation must not
perturb the system it observes*:

  * :mod:`repro.obs.metrics` — ``Counter``/``Gauge``/``Histogram`` in a
    ``Registry`` with JSON snapshot + Prometheus text exposition; fixed
    memory (streaming histograms), wall-clock-free export.
  * :mod:`repro.obs.trace` — spans on two clocks: per-request per-stage
    spans on the executor's deterministic tick clock, exported as Chrome
    ``trace_event`` JSON (Perfetto-viewable, byte-identical across
    same-seed runs, zero-cost when disabled), and ``span``, a host span on
    the profiler's clock beside the device's operations (a TraceMe check
    when no profile runs), plus the log of released requests' stage
    stamps (``RELEASED``).
  * :mod:`repro.obs.events` — typed dependability events (strike /
    detection / rollback / recovery / quarantine / failover) with fault
    provenance, plus injection→detection→recovery timeline reconstruction
    and per-policy latency distributions.

See docs/observability.md for the span model, event schema, and Perfetto
workflow.
"""
from repro.obs.events import Event, EventLog
from repro.obs.metrics import (Counter, Gauge, Histogram, Registry,
                               exp_buckets)
from repro.obs.trace import SpanTracer, dump_merged, merge_traces, span

__all__ = [
    "Counter", "Gauge", "Histogram", "Registry", "exp_buckets",
    "SpanTracer", "merge_traces", "dump_merged", "span", "Event", "EventLog",
]
