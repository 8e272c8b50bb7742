"""Unified telemetry: on-device counter plane, span tracing, metrics
registry, and the time plane (host spans and named scopes on the
profiler's clock, the compile counter).  See ``obs/README.md`` for the
design and the trace invariants ``tools/trace_report.py`` enforces."""
from repro.obs.counters import (Counters, HOST_COUNTERS, delta,
                                host_counters_scope, note_free, note_host,
                                snapshot, update_token_counters)
from repro.obs.registry import MetricsRegistry
from repro.obs.timeplane import (COMPILE_STATS, SCOPES, compile_stats,
                                 fresh_hlo_text, listen_compiles,
                                 program_scopes, publish_scopes, scope_map,
                                 scope_of, span)
from repro.obs.trace import Tracer, read_trace

__all__ = [
    "Counters", "HOST_COUNTERS", "delta", "host_counters_scope",
    "note_free", "note_host", "snapshot", "update_token_counters",
    "MetricsRegistry", "Tracer", "read_trace",
    "COMPILE_STATS", "SCOPES", "compile_stats", "fresh_hlo_text",
    "listen_compiles",
    "program_scopes", "publish_scopes", "scope_map", "scope_of", "span",
]
