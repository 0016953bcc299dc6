"""Telemetry subsystem: live metrics, Prometheus/jsonl export, flight-recorder
tracing.

The observability layer wired through every other subsystem (runner,
governor, io pipeline, serve scheduler — see README "Telemetry"):

* :mod:`.metrics` — thread-safe registry of labeled counters / gauges /
  log-bucketed histograms (percentiles without sample retention), snapshot/
  delta views, multihost root aggregation, and the :class:`ThroughputMonitor`
  SLO baseline behind the journal's ``perf_degraded`` event,
* :mod:`.exporters` — Prometheus text exposition (served from
  ``GET /metrics`` on the HTTP front) + the cadenced ``metrics.jsonl``
  run-dir dump for headless runs,
* :mod:`.tracing` — ~ns-overhead-when-disabled ``span()`` API feeding a
  bounded flight recorder, auto-dumped as Perfetto ``traceEvents`` JSON on
  DispatchHang / DivergenceError / SIGTERM drain / unclean exit.

Hard contract (CI-asserted): telemetry records host-side values the
run already computed — it never touches traced programs, and instrumented
runs are bit-identical to ``RUSTPDE_TELEMETRY=0`` runs.
"""

from .exporters import (  # noqa: F401
    PROMETHEUS_CONTENT_TYPE,
    MetricsDumper,
    prometheus_text,
    read_metrics_jsonl,
)
from .metrics import (  # noqa: F401
    REGISTRY,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    ThroughputMonitor,
    counter,
    default_registry,
    gather_global_snapshot,
    gauge,
    histogram,
    merge_snapshots,
    snapshot,
)
from .metrics import enabled as metrics_enabled  # noqa: F401
from .metrics import set_enabled as set_metrics_enabled  # noqa: F401
from .tracing import (  # noqa: F401
    RECORDER,
    FlightRecorder,
    arm_exit_dump,
    dump_flight_record,
    instant,
    span,
)
from .tracing import enabled as tracing_enabled  # noqa: F401
from .tracing import set_enabled as set_tracing_enabled  # noqa: F401
from . import compile_log  # noqa: F401
from . import reqtrace  # noqa: F401
from .reqtrace import assemble_request_trace  # noqa: F401
from .reqtrace import mint as mint_trace_context  # noqa: F401
from .reqtrace import enabled as reqtrace_enabled  # noqa: F401
from .reqtrace import set_enabled as set_reqtrace_enabled  # noqa: F401


def set_enabled(flag: bool) -> None:
    """Master switch: metrics AND tracing AND request tracing together
    (the OFF leg of the bit-identity tests; ``RUSTPDE_TELEMETRY=0`` / ``RUSTPDE_TRACE=0``
    / ``RUSTPDE_REQTRACE=0`` set the per-layer defaults at import)."""
    set_metrics_enabled(flag)
    set_tracing_enabled(flag)
    set_reqtrace_enabled(flag)


def enabled() -> bool:
    """True when any layer records."""
    return metrics_enabled() or tracing_enabled() or reqtrace_enabled()
