"""repro.obs — tracing, fixed-boundary histograms, Prometheus text tools.

The observability layer the service/fleet/scene tiers share. Import-light
on purpose (stdlib only): ``repro.engine`` and ``repro.service`` both use
it, so it must sit below every other repro package in the import graph.
"""

from repro.obs.histogram import (
    DEFAULT_LATENCY_BOUNDS,
    DISPATCH_BOUNDS,
    Histogram,
    HistogramSnapshot,
    empty_snapshot,
)
from repro.obs.promtext import (
    PromBuilder,
    PromPage,
    PromSample,
    base_family,
    escape_label_value,
    format_le,
    format_value,
    parse_prom_text,
    unescape_label_value,
)
from repro.obs.trace import (
    NULL_TRACE,
    FlightRecorder,
    Span,
    Trace,
    auto_dump,
    configure,
    join_trace,
    maybe_trace,
    mono_to_wall_us,
    new_trace_id,
    recorder,
    tracing_enabled,
    use_trace,
)

__all__ = [
    "DEFAULT_LATENCY_BOUNDS",
    "DISPATCH_BOUNDS",
    "Histogram",
    "HistogramSnapshot",
    "empty_snapshot",
    "PromBuilder",
    "PromPage",
    "PromSample",
    "base_family",
    "escape_label_value",
    "format_le",
    "format_value",
    "parse_prom_text",
    "unescape_label_value",
    "NULL_TRACE",
    "FlightRecorder",
    "Span",
    "Trace",
    "auto_dump",
    "configure",
    "join_trace",
    "maybe_trace",
    "mono_to_wall_us",
    "new_trace_id",
    "recorder",
    "tracing_enabled",
    "use_trace",
]
