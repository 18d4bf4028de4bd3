"""Unified observability layer: metrics registry + request tracing.

Dependency-free on purpose — ``obs`` sits below every other prime_tpu layer
(core.client, serve, evals all record into it) so it must import nothing from
them and nothing heavyweight (no jax, no httpx, no pydantic). Knob reads go
through the stdlib-only ``prime_tpu.utils.env`` leaf, which keeps that
property while still satisfying the knob-registry lint (core.config
re-exports the same helpers as the canonical surface for everything above
this layer). Two halves:

- :mod:`prime_tpu.obs.metrics` — ``Counter`` / ``Gauge`` / ``Histogram``
  families in a ``Registry`` with one lock per registry, so a snapshot (or a
  Prometheus scrape) sees a mutually consistent view of every series.
- :mod:`prime_tpu.obs.trace` — a lightweight span tracer
  (``span(name, **attrs)`` context manager) with monotonic-clock timing,
  thread-local parent/child nesting and JSONL export for offline analysis.
- :mod:`prime_tpu.obs.timeseries` — rolling rings of registry snapshots
  with windowed rate/quantile queries (the observatory's memory).
- :mod:`prime_tpu.obs.slo` — declarative SLO policies evaluated with
  multi-window burn rates into typed ``ScaleSignal`` recommendations.
- :mod:`prime_tpu.obs.phase` — the engine loop's phase clock: every second
  of the loop thread charged to one phase, the same sites as annotations in
  a ``jax.profiler`` capture, and program builds accounted through
  ``jax.monitoring``.
- :mod:`prime_tpu.obs.profiler` — the device-time observatory: a sampled
  ``block_until_ready`` step clock under the serving engine, HBM
  accounting, cost-model MFU attribution, and Chrome-trace export
  (``jax`` is imported lazily inside its fencing paths, so this package
  stays importable without it).

See docs/architecture.md "Observability" for the exposition endpoints
(`GET /metrics?format=prometheus`, `/healthz`) and the trace JSONL schema.
"""

from prime_tpu.obs.flight import FlightRecorder
from prime_tpu.obs.metrics import (
    DEFAULT_LATENCY_BUCKETS,
    DEFAULT_SIZE_BUCKETS,
    REGISTRY,
    Counter,
    Gauge,
    Histogram,
    Registry,
    counter_delta,
    hist_delta,
    lint_prometheus_text,
    merge_hists,
    quantile_from_snapshot,
)
from prime_tpu.obs.slo import (
    ScaleSignal,
    SloEvaluator,
    SloPolicy,
    default_policies,
)
from prime_tpu.obs.phase import PhaseClock
from prime_tpu.obs.profiler import DeviceProfiler, chrome_trace
from prime_tpu.obs.timeseries import RegistrySampler, SnapshotRing
from prime_tpu.obs.trace import (
    TRACEPARENT_HEADER,
    TRACER,
    Span,
    TraceContext,
    Tracer,
    new_traceparent,
    parse_traceparent,
    span,
)

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "Registry",
    "REGISTRY",
    "DEFAULT_LATENCY_BUCKETS",
    "DEFAULT_SIZE_BUCKETS",
    "lint_prometheus_text",
    "quantile_from_snapshot",
    "counter_delta",
    "hist_delta",
    "merge_hists",
    "RegistrySampler",
    "ScaleSignal",
    "SloEvaluator",
    "SloPolicy",
    "SnapshotRing",
    "default_policies",
    "FlightRecorder",
    "DeviceProfiler",
    "PhaseClock",
    "chrome_trace",
    "Span",
    "TraceContext",
    "Tracer",
    "TRACER",
    "TRACEPARENT_HEADER",
    "new_traceparent",
    "parse_traceparent",
    "span",
]
