"""pathway_tpu.obs — request-scoped tracing + the always-on flight
recorder (Round-11; see obs/tracer.py for the span model) and the
device cost observatory (Round-14): per-program kernel profiles
(obs/profiler.py), the HBM ledger with pre-flight fit checks
(obs/memory.py), and the persistent cost-model store (obs/costdb.py)."""

from . import costdb, memory, profiler  # noqa: F401
from .tracer import (  # noqa: F401
    FlightRecorder,
    Span,
    chrome_trace_dump,
    context_from_trace_header,
    current_context,
    disabled,
    event,
    export_otlp,
    maybe_start_flusher_from_env,
    new_trace_id,
    phase,
    record_span,
    recorder,
    reset_current,
    sanitize_trace_id,
    set_current,
    shutdown,
    span,
    start_flusher,
    start_span,
    trace_annotation,
    use_context,
)

__all__ = [
    "costdb", "memory", "profiler",
    "FlightRecorder", "Span", "chrome_trace_dump",
    "context_from_trace_header", "current_context", "disabled", "event",
    "export_otlp", "maybe_start_flusher_from_env", "new_trace_id",
    "phase", "record_span", "recorder", "reset_current",
    "sanitize_trace_id", "set_current", "shutdown", "span", "start_flusher",
    "start_span", "trace_annotation", "use_context",
]
