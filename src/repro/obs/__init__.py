"""Observability layer for the serving stack — tracing, metrics, exporters.

The paper's claim is a latency/quality trade measured end-to-end; the serving
stack realizing it (HTTP admission → futures → κ-waves → engines →
fixed-point iteration) could only report lifetime aggregates.  This package
is the time-resolved counterpart, with memory O(1) in queries served:

``metrics.py``   bounded instruments (Counter/Gauge/Histogram/Reservoir) in
                 a ``MetricsRegistry`` with label support and a series cap —
                 what ``ServiceTelemetry`` stores its state in.
``trace.py``     span-based tracer with injected clocks: every query carries
                 a trace (submit → cache probe → admission wait → wave
                 execute → resolution) cross-linked with a per-wave trace
                 (plan → iterate w/ early-exit residual → top-K → resolve);
                 ``stage()`` times a layer boundary once for telemetry, the
                 wave trace and the ``jax.profiler`` device profile.
``recorder.py``  flight recorder: ring buffers of the last N completed
                 traces and admission-control transitions, so a shed/degrade
                 incident can be reconstructed after the fact.
``export.py``    Prometheus text exposition (``GET /v1/metrics``), JSON
                 dumps, and terminal-friendly trace/SLO rendering.
``slo.py``       declarative SLO specs (latency / shed rate / shadow
                 quality) evaluated over sliding windows by an
                 injected-clock ``SLOMonitor`` with multi-window error-budget
                 burn-rate alerting — the layer that makes the instruments
                 actionable.
``otlp.py``      stdlib-only OTLP/HTTP-JSON exporter: spans via a fan-out
                 ``Tracer`` sink beside the flight recorder, metrics via a
                 periodic delta-temporality push.

Everything is clock-injected and deterministic under test; nothing here
imports jax at import time (``stage()`` loads ``jax.profiler`` on first
use) — the observability layer must never be the thing that makes
the hot path slow or the test suite heavy.
"""
from repro.obs.export import (
    format_event,
    format_slo,
    format_trace,
    prometheus_text,
)
from repro.obs.metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    Reservoir,
    exponential_buckets,
)
from repro.obs.otlp import OTLPExporter
from repro.obs.recorder import FlightRecorder
from repro.obs.slo import SLOMonitor, SLOSpec, default_slo_specs
from repro.obs.trace import Span, Trace, Tracer, fanout_sink, stage

__all__ = [
    "Counter", "Gauge", "Histogram", "Reservoir", "MetricsRegistry",
    "exponential_buckets",
    "Span", "Trace", "Tracer", "fanout_sink", "stage",
    "FlightRecorder",
    "SLOSpec", "SLOMonitor", "default_slo_specs",
    "OTLPExporter",
    "prometheus_text", "format_trace", "format_event", "format_slo",
]
