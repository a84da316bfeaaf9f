"""``chainermn_tpu.monitor`` — the unified telemetry subsystem.

The reference ChainerMN ships no observability of its own (SURVEY.md S5:
users bolt on Chainer hooks + nvprof; a lost collective hangs silently).
PR 1 left good but disconnected primitives (``extensions.profiling``,
``serving.metrics``); this package is the spine that connects them, in
four pillars:

- **Metrics** (:class:`MetricsRegistry`): process-wide counters / gauges /
  histograms with labels, JSON :func:`snapshot`, Prometheus-style
  :func:`exposition`, and cross-rank :func:`aggregate` (fleet-wide p50/p99
  on rank 0 over the communicator's object transport, merged with the
  ``latency_report`` field convention).
- **Events** (:class:`EventLog`): a bounded ring of structured events
  (step start/end, prefill/decode, slot admit/retire, compile, watchdog
  arm/fire) dumped automatically — last N events + per-device
  ``memory_stats()`` — when ``Watchdog`` fires or ``global_except_hook``
  trips.
- **Profiler annotations** (:func:`annotate`): ``TraceAnnotation`` +
  ``named_scope`` in one context manager (no-op fallback on legacy JAX),
  permanently on inside train steps, serving prefill/decode, the
  scheduler's admit loop, and every ``MeshCommunicator`` collective.
- **Recompile + memory tracking** (:class:`RecompileGuard`,
  :func:`record_memory_gauges`): executable-cache growth as a counted,
  event-logged signal (the serving zero-recompile assertion, generalized),
  plus periodic device-memory gauges.
- **Request-scoped tracing** (:class:`Tracer` / :func:`get_tracer`):
  Dapper-style span trees with context propagation — a serving request's
  queue -> admit -> prefill -> decode -> retire, a training step's
  prefetch-wait -> dispatch -> loss fetch — head-sampled with forced
  retention on error/deadline miss, exported as Chrome trace-event JSON
  (Perfetto-loadable).
- **SLO engine** (:class:`SLOEngine`): declarative latency / error-rate
  objectives evaluated from registry histograms and counters with
  multi-window burn rates; breaches emit flight-recorder events naming
  the offending trace ids, and ``slo_burn_rate`` gauges pool fleet-wide
  through :func:`aggregate`.
- **Continuous telemetry** (:class:`TimeSeriesStore` / :class:`Collector`
  / :class:`HealthMonitor`): a fixed-cadence collector samples every
  registry instrument into bounded ring-buffer series (counters as
  rates, histograms as windowed p50/p99), a declarative derived-signal
  graph (:class:`Rate` / :class:`EWMA` / :class:`Ratio` /
  :class:`WindowPercentile`) feeds edge-triggered detectors (z-score
  drift, thresholds, decode-stall deadman), and detector states compose
  into per-replica ``healthy``/``degraded``/``critical`` scores the
  fleet router consults as a routing penalty (:func:`fleet_health`).
- **Scrape endpoint** (:func:`chainermn_tpu.monitor.http.serve`):
  stdlib-only background HTTP server exposing ``/metrics`` (Prometheus
  text), ``/traces`` (Chrome JSON), ``/slo``, ``/events``,
  ``/timeseries``, and ``/health``.

The per-step hot-path cost is a few dict/deque operations; everything
heavier happens at reporting or failure time. What it costs a served decode
step on the chip is the benchmark's ``host_gap_account_ms.decode``.

Usage::

    from chainermn_tpu import monitor

    step = monitor.instrument(step, "train")      # events+metrics+recompiles
    with monitor.annotate("chainermn.eval"):      # profiler region
        ...
    monitor.emit("checkpoint", path=p)            # structured event
    print(monitor.exposition())                   # Prometheus text
    record["monitor"] = monitor.snapshot()        # JSON block
    fleet = monitor.aggregate(comm)               # rank-0 fleet percentiles
"""

from __future__ import annotations

from chainermn_tpu.monitor._state import get_event_log, get_registry
from chainermn_tpu.monitor.annotations import annotate
from chainermn_tpu.monitor.costs import (
    CostLedger,
    NoisyNeighborDetector,
    merge_cost_payloads,
    standard_tenant_sensors,
)
from chainermn_tpu.monitor.events import EventLog, device_memory_lines
from chainermn_tpu.monitor.health import (
    HealthMonitor,
    HealthScore,
    fleet_health,
    standard_replica_sensors,
)
from chainermn_tpu.monitor.instrument import (
    MonitoredFunction,
    RecompileGuard,
    instrument,
    record_memory_gauges,
)
from chainermn_tpu.monitor.registry import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    merge_rank_payloads,
)
from chainermn_tpu.monitor.slo import (
    ErrorRateObjective,
    LatencyObjective,
    SLOEngine,
    get_slo_engine,
)
from chainermn_tpu.monitor.timeseries import (
    Collector,
    DeadmanDetector,
    Detector,
    EWMA,
    Rate,
    Ratio,
    ThresholdDetector,
    TimeSeriesStore,
    WindowPercentile,
    ZScoreDetector,
)
from chainermn_tpu.monitor.trace import Span, Trace, Tracer, get_tracer
from chainermn_tpu.monitor import http  # noqa: F401 — monitor.http.serve


def emit(kind: str, **fields) -> None:
    """Emit a structured event into the default flight recorder."""
    get_event_log().emit(kind, **fields)


def snapshot(memory: bool = True) -> dict:
    """JSON-able snapshot of the default registry (refreshing the
    device-memory gauges first unless ``memory=False``)."""
    if memory:
        record_memory_gauges(get_registry())
    return get_registry().snapshot()


def exposition() -> str:
    """Prometheus text exposition of the default registry."""
    return get_registry().exposition()


def aggregate(comm) -> dict:
    """Fleet-wide merge of the default registry across ranks (counters
    summed, gauges averaged, histogram percentiles over pooled samples)."""
    return get_registry().aggregate(comm)


__all__ = [
    "Collector",
    "CostLedger",
    "Counter",
    "DeadmanDetector",
    "Detector",
    "EWMA",
    "ErrorRateObjective",
    "EventLog",
    "Gauge",
    "HealthMonitor",
    "HealthScore",
    "Histogram",
    "LatencyObjective",
    "MetricsRegistry",
    "MonitoredFunction",
    "NoisyNeighborDetector",
    "Rate",
    "Ratio",
    "RecompileGuard",
    "SLOEngine",
    "Span",
    "ThresholdDetector",
    "TimeSeriesStore",
    "Trace",
    "Tracer",
    "WindowPercentile",
    "ZScoreDetector",
    "aggregate",
    "annotate",
    "device_memory_lines",
    "emit",
    "exposition",
    "fleet_health",
    "get_event_log",
    "get_registry",
    "get_slo_engine",
    "get_tracer",
    "http",
    "instrument",
    "merge_cost_payloads",
    "merge_rank_payloads",
    "record_memory_gauges",
    "snapshot",
    "standard_replica_sensors",
    "standard_tenant_sensors",
]
