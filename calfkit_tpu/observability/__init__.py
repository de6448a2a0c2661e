"""Observability: tracing, metrics, and the engine flight recorder.

Four pieces:

- :mod:`~calfkit_tpu.observability.trace` — ``TraceContext`` propagation
  over Kafka record headers, spans, the process tracer with its bounded
  ring buffer (zero-broker fallback), and the ``mesh.traces`` export seam.
- :mod:`~calfkit_tpu.observability.metrics` — the dependency-free
  counter/gauge/histogram registry and Prometheus text exposition
  (``metrics_text``).
- :mod:`~calfkit_tpu.observability.flightrec` — the engine flight
  recorder: a bounded ring journal of scheduler events, dumped to JSONL
  on engine fault / SIGUSR2 / ``GET /flightrec`` and reconstructed per
  request by ``ck timeline``.
- :mod:`~calfkit_tpu.observability.http` — the optional asyncio endpoint:
  ``/metrics``, ``/healthz`` (liveness), ``/readyz`` (readiness probe),
  ``/flightrec``, ``/capacity``, ``/profile``.
- :mod:`~calfkit_tpu.observability.devtrace` — a few seconds of
  ``jax.profiler`` reduced to device seconds by the program's named
  scopes and idle gaps by the engine's phase (``GET /profile``).
- :mod:`~calfkit_tpu.observability.runledger` — run-scoped observability
  (ISSUE 17): the client-side per-run attempt ledger behind
  ``handle.run_report()`` and the compacted ``mesh.runs`` export, plus
  the pure SLO rollup fold behind ``mesh.slo`` / ``ck slo``.

Everything here is fail-open: telemetry errors never fault serving.
"""

from calfkit_tpu.observability.metrics import (
    REGISTRY,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    metrics_text,
)
from calfkit_tpu.observability.trace import (
    TRACER,
    Span,
    TraceContext,
    Tracer,
    current_context,
)
from calfkit_tpu.observability.flightrec import FlightRecorder
from calfkit_tpu.observability.http import MetricsServer
from calfkit_tpu.observability.runledger import (
    RunLedger,
    RunWindowStore,
    rollup_window,
    run_window_store,
)

__all__ = [
    "FlightRecorder",
    "RunLedger",
    "RunWindowStore",
    "rollup_window",
    "run_window_store",
    "REGISTRY",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "MetricsServer",
    "metrics_text",
    "TRACER",
    "Span",
    "TraceContext",
    "Tracer",
    "current_context",
]
