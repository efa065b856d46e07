"""``paddle_tpu.observability`` — unified runtime metrics + tracing.

The measurement substrate for the serving engine, elastic launcher, and
training loop: a thread-safe metric registry (`metrics`), a host-span
tracer with chrome-trace export (`trace`), distributed trace-context
propagation + cross-process trace merging (`tracing`), Prometheus/
JSON/HTTP exporters (`export`), the XLA compile watcher +
device-memory gauges (`compile_watch`), the crash flight recorder
(`flight_recorder`), the SLO burn-rate engine (`slo`), and build
identity, the device-peak table and on-demand profiler capture (`perf`).
``PADDLE_TPU_METRICS=0`` turns the whole layer into no-ops. See README
"Observability" for the standard metric names.
"""

from . import (  # noqa: F401
    compile_watch, export, flight_recorder, metrics, perf, slo, trace,
    tracing,
)
from .compile_watch import (  # noqa: F401
    sample_device_memory, watch, watched_jit,
)
from .export import (  # noqa: F401
    json_snapshot, prometheus_text, snapshot_to_prometheus,
    start_http_server,
)
from .metrics import (  # noqa: F401
    Counter, Gauge, Histogram, MetricsRegistry, counter, default_registry,
    enabled, gauge, histogram,
)
from .perf import (  # noqa: F401
    build_info, capture_bundle, capture_local, device_peaks,
    ensure_build_info,
)
from .slo import SloEngine, SloSpec, histogram_quantile  # noqa: F401
from .trace import export_chrome_trace, span  # noqa: F401
from .tracing import (  # noqa: F401
    TraceContext, activate, adopt, current, format_traceparent,
    parse_traceparent,
)

__all__ = [
    "metrics", "trace", "tracing", "export", "compile_watch",
    "flight_recorder", "slo", "perf",
    "TraceContext", "current", "activate", "adopt",
    "parse_traceparent", "format_traceparent",
    "SloEngine", "SloSpec", "histogram_quantile",
    "device_peaks", "build_info", "ensure_build_info",
    "capture_local", "capture_bundle",
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "counter", "gauge", "histogram", "default_registry", "enabled",
    "span", "export_chrome_trace",
    "prometheus_text", "json_snapshot", "snapshot_to_prometheus",
    "start_http_server",
    "watch", "watched_jit", "sample_device_memory",
]
