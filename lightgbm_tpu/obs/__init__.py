"""Unified observability plane: seams on the profiler's clock and the
single process metrics registry (docs/OBSERVABILITY.md).

Two pillars, shared by training, serving and resilience:

- ``obs.trace`` — ``span(name, ...)``, the one seam API: a
  ``jax.profiler.TraceAnnotation`` on the profiler's clock always, a
  flight-ring record for the coarse seams, a ``global_timer`` section on
  request, and a Chrome trace-event / Perfetto JSON recorder behind
  ``LIGHTGBM_TPU_TRACE``;
- ``obs.metrics`` — the ``MetricsRegistry`` promoted from serving as the
  process-wide instrument registry (``global_registry``), with JSON
  snapshots and Prometheus text exposition.

The ACTIVE layer on top (docs/OBSERVABILITY.md):

- ``obs.flight`` — always-on bounded ring-buffer flight recorder
  dumping atomic forensic bundles on failure triggers;
- ``obs.watchdog`` — heartbeat/SLO sentry (stalls, trees/sec floor,
  serving-p99 ceiling) breaching into ``slo_breach_total`` + flight
  dumps;
- ``obs.aggregate`` — pod-level telemetry vectors gathered through the
  resilient collective plane (straggler skew, per-tier byte sums);
- ``obs.diagnose`` — ranked bottleneck verdicts joining measured vs
  planner-predicted signals (``tools/obs_doctor.py`` CLI);
- ``obs.http`` — opt-in stdlib HTTP exposition of the process registry.

``metrics``/``flight``/``watchdog``/``http`` are stdlib-only; ``trace``
is stdlib-only at import and looks ``jax.profiler`` up at the first span.
"""

from .metrics import (LATENCY_BUCKETS_MS, RATIO_BUCKETS, Counter, Gauge,
                      Histogram, MetricsRegistry, get_registry,
                      global_registry)
from .trace import (Tracer, global_tracer, instant, span, span_coverage,
                    trace_enabled, trace_path)
# importing flight installs the tracer's ring tee (set_flight_sink)
from .flight import FlightRecorder, global_flight
from .watchdog import SLOConfig, Watchdog, global_watchdog

__all__ = [
    "span", "instant", "trace_enabled", "trace_path", "span_coverage",
    "Tracer", "global_tracer",
    "MetricsRegistry", "global_registry", "get_registry",
    "Counter", "Gauge", "Histogram",
    "LATENCY_BUCKETS_MS", "RATIO_BUCKETS",
    "FlightRecorder", "global_flight",
    "Watchdog", "SLOConfig", "global_watchdog",
]
