"""Observability: distributed query tracing (qtrace), the metrics catalog,
and the Prometheus exposition sink. See trace.py for the span model (wall
and thread-CPU time a span: `durationMs`, `attrs.cpuMs`), the propagation
contract and the one cap policy of store and collector, dispatch.py for the
dispatch and backend-compile counters (and qtrace's dropped-span count
beside them), catalog.py for the declared metric names the druidlint
`metric-name` rule enforces, prometheus.py for /metrics. PERF.md §3 maps
every span and counter to the metric or operator use it is for."""
from druid_tpu.obs.catalog import METRICS, render_table
from druid_tpu.obs.prometheus import MetricRegistry
from druid_tpu.obs.trace import (Span, TraceStore, attach, current_span,
                                 late_span, root_span, span, trace_store,
                                 with_traceparent)

__all__ = [
    "METRICS", "render_table", "MetricRegistry",
    "Span", "TraceStore", "attach", "current_span", "late_span",
    "root_span", "span", "trace_store", "with_traceparent",
]
