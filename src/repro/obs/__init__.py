"""Process-wide telemetry: spans, counters, host stalls, decision log,
drift checks.

Aggregates (counters, gauges, histograms) and the host-stall record
(JAX compiles and GC pauses, ``host_stalls()``) are always on.  A
tracing session gates what costs — the span/instant event buffer, the
decision log and the export — and is off by default, when ``span``
hands out a shared null object.  Open one with ``obs.tracing(path)``
(context manager), the ``--trace`` flags on ``apps/gnn`` /
``benchmarks/run.py`` / ``decider_train``, or ``REPRO_TRACE=path`` in the
environment; read the exported Chrome-trace JSON in Perfetto or with
``python -m repro.apps.obs_report``.  Every time is ``time.time_ns()``
wall clock, the clock a JAX profiler trace is anchored to.

See docs/OBSERVABILITY.md for the span/counter inventory and the
decision-log schema.
"""
from repro.obs.trace import (
    tracing, start_tracing, stop_tracing, trace_enabled,
    span, instant, export_trace, trace_events,
)
from repro.obs.metrics import (
    counter, gauge, histogram,
    metrics_snapshot, reset_metrics, intercept_pallas,
)
from repro.obs.stalls import HostStall, host_stalls
from repro.obs.decisions import (
    DecisionRecord, DriftAdvisory, DRIFT_FEATURES, DRIFT_THRESHOLD,
    record_decision, decision_log, clear_decisions,
    graph_snapshot, check_drift, resolve_drift_thresholds,
)
from repro.obs.trace import _env_autostart
from repro.obs.stalls import _install as _install_stall_listeners

__all__ = [
    # trace
    "tracing", "start_tracing", "stop_tracing", "trace_enabled",
    "span", "instant", "export_trace", "trace_events",
    # metrics
    "counter", "gauge", "histogram",
    "metrics_snapshot", "reset_metrics", "intercept_pallas",
    # host stalls
    "HostStall", "host_stalls",
    # decisions
    "DecisionRecord", "DriftAdvisory", "DRIFT_FEATURES", "DRIFT_THRESHOLD",
    "record_decision", "decision_log", "clear_decisions",
    "graph_snapshot", "check_drift", "resolve_drift_thresholds",
]

_install_stall_listeners()
_env_autostart()
del _env_autostart, _install_stall_listeners
