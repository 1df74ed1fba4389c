"""repro.obs — overlap-proving trace and telemetry subsystem.

Always-compiled-in instrumentation for the activation-offload path:
a lock-light per-thread ring tracer (`repro.obs.tracer`), a
Chrome/Perfetto exporter + validator (`repro.obs.export`), and the
overlap analyzer that turns a trace window into I/O-hidden fraction and
stall attribution (`repro.obs.overlap`).

Call sites use the module-level helpers (`span`/`instant`/`count`/
`gauge`/`current_span`), which are a None-check no-op until `enable()`
installs a tracer — usually via `TrainSession(trace=...)` or `--trace`.
`repro.obs.compiles.CompileCounter` counts XLA backend compiles.
"""
from repro.obs.tracer import (
    DEFAULT_RING_SIZE,
    Tracer,
    count,
    current_span,
    disable,
    enable,
    gauge,
    get_tracer,
    instant,
    is_enabled,
    span,
)
from repro.obs.export import trace_events, validate_trace, write_chrome_trace
from repro.obs.overlap import analyze, predicted_vs_measured

__all__ = [
    "DEFAULT_RING_SIZE",
    "Tracer",
    "analyze",
    "count",
    "current_span",
    "disable",
    "enable",
    "gauge",
    "get_tracer",
    "instant",
    "is_enabled",
    "predicted_vs_measured",
    "span",
    "trace_events",
    "validate_trace",
    "write_chrome_trace",
]
