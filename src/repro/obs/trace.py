"""Nestable spans + instant events with Chrome-trace-event JSON export.

The session switch gates what costs: the span/instant event buffer, the
decision log and the export.  It is off by default; then ``span``
returns a shared null context manager and ``instant`` returns
immediately.  Counters, gauges, histograms and the host-stall record
(``repro.obs.stalls``) stay on either way.  Open a session with the
``tracing(path)`` context manager, ``start_tracing()``/``stop_tracing()``,
or the ``REPRO_TRACE=path`` environment variable (checked once at import;
the trace is written atexit).

Every time is taken from ``time.time_ns()``, the wall clock a JAX
profiler trace is anchored to, so a session's start (``t0_ns``, exported
under ``otherData``) plus an event's offset is an epoch time.

Exported files follow the Chrome trace event format — ``"X"`` complete
events (``ts``/``dur`` in microseconds from the session start) nest by
containment per thread, ``"i"`` instant events mark points in time, and
one ``"C"`` counter event per metric series is appended at export so
Perfetto / ``chrome://tracing`` render the final counter values.  Two
extra top-level keys, ``repro_metrics`` and ``repro_decisions``, carry
the full metric snapshot and the structured decision log (extra keys are
legal in the format and ignored by viewers).
"""
from __future__ import annotations

import json
import os
import threading
from time import time_ns
from typing import Any, Optional

__all__ = [
    "tracing", "start_tracing", "stop_tracing", "trace_enabled",
    "span", "instant", "export_trace", "trace_events",
]

_LOCK = threading.Lock()
_STATE: Optional["_TraceState"] = None


class _TraceState:
    __slots__ = ("events", "t0_ns", "path")

    def __init__(self, path=None):
        self.events: list[dict] = []
        self.t0_ns = time_ns()
        self.path = path

    def now_us(self) -> float:
        return (time_ns() - self.t0_ns) * 1e-3

    def add(self, event: dict) -> None:
        with _LOCK:
            self.events.append(event)


def trace_enabled() -> bool:
    """True while a tracing session is active (the switch that gates the
    event buffer, the decision log and the export)."""
    return _STATE is not None


class _Span:
    """Context manager emitting one ``"X"`` complete event on exit."""

    __slots__ = ("_state", "_name", "_cat", "_args", "_ts")

    def __init__(self, state, name, cat, args):
        self._state, self._name, self._cat, self._args = \
            state, name, cat, args

    def __enter__(self):
        self._ts = self._state.now_us()
        return self

    def __exit__(self, *exc):
        st = self._state
        st.add({
            "name": self._name, "cat": self._cat, "ph": "X",
            "ts": self._ts, "dur": st.now_us() - self._ts,
            "pid": os.getpid(), "tid": threading.get_ident(),
            "args": self._args,
        })
        return False


class _NullSpan:
    """Shared no-op span handed out while tracing is disabled."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


def span(name: str, cat: str = "repro", **args: Any):
    """Open a nestable span: ``with span("gnn.step", step=i): ...``.
    Returns a shared null context manager when tracing is disabled."""
    st = _STATE
    if st is None:
        return _NULL_SPAN
    return _Span(st, name, cat, args)


def instant(name: str, cat: str = "repro", **args: Any) -> None:
    """Record a point-in-time ``"i"`` event (no-op when disabled)."""
    st = _STATE
    if st is None:
        return
    st.add({
        "name": name, "cat": cat, "ph": "i", "s": "t",
        "ts": st.now_us(),
        "pid": os.getpid(), "tid": threading.get_ident(),
        "args": args,
    })


def trace_events() -> list[dict]:
    """Snapshot of the event buffer (empty list when disabled)."""
    st = _STATE
    if st is None:
        return []
    with _LOCK:
        return list(st.events)


def _jsonable(obj):
    """json.dump fallback: numpy scalars/arrays, tuples-in-sets, etc."""
    if hasattr(obj, "item"):          # numpy scalar
        return obj.item()
    if hasattr(obj, "tolist"):        # numpy array
        return obj.tolist()
    if isinstance(obj, (set, frozenset)):
        return sorted(obj)
    return str(obj)


def start_tracing(path: Optional[str] = None) -> None:
    """Open a tracing session: fresh event buffer, metrics registry and
    decision log reset (a trace captures its own window).  Raises if a
    session is already active."""
    global _STATE
    if _STATE is not None:
        raise RuntimeError("tracing already active")
    from repro.obs import decisions as _decisions, metrics as _metrics
    _STATE = _TraceState(path)
    _metrics.reset_metrics()
    _decisions.clear_decisions()


def export_trace(path: str) -> str:
    """Write the current buffer + metric snapshot + decision log as
    Chrome-trace JSON without stopping the session.  Returns ``path``."""
    from repro.obs import decisions as _decisions, metrics as _metrics
    st = _STATE
    events = trace_events()
    end_us = st.now_us() if st is not None else 0.0
    pid = os.getpid()
    snapshot = _metrics.metrics_snapshot()
    for mname, series in sorted(snapshot.items()):
        for labels, value in sorted(series.items()):
            if isinstance(value, dict):          # histogram stats
                value = value.get("sum", 0.0)
            disp = f"{mname}{{{labels}}}" if labels else mname
            events.append({"name": disp, "ph": "C", "ts": end_us,
                           "pid": pid, "tid": 0,
                           "args": {"value": value}})
    payload = {
        "traceEvents": events,
        "displayTimeUnit": "ms",
        "repro_metrics": snapshot,
        "repro_decisions": [r.to_dict() for r in _decisions.decision_log()],
        "otherData": {"walltime": time_ns() * 1e-9,
                      "t0_ns": st.t0_ns if st is not None else None},
    }
    with open(path, "w") as fh:
        json.dump(payload, fh, indent=1, default=_jsonable)
    return path


def stop_tracing(path: Optional[str] = None) -> Optional[str]:
    """End the session; write the trace to ``path`` (or the path given
    at start) if any.  The decision log survives the stop so
    ``check_drift`` can run against it later.  Returns the written path."""
    global _STATE
    st = _STATE
    if st is None:
        return None
    out = path or st.path
    written = export_trace(out) if out else None
    _STATE = None
    return written


class _Tracing:
    """``with tracing(path):`` — start on enter, write + stop on exit."""

    def __init__(self, path=None):
        self._path = path

    def __enter__(self):
        start_tracing(self._path)
        return self

    def __exit__(self, *exc):
        stop_tracing()
        return False


def tracing(path: Optional[str] = None) -> "_Tracing":
    """Context manager enabling the obs layer for its body; exports the
    Chrome-trace JSON to ``path`` on exit when one is given."""
    return _Tracing(path)


def _env_autostart() -> None:
    """``REPRO_TRACE=trace.json`` starts a process-lifetime session whose
    trace is written at interpreter exit (called once from
    ``repro.obs.__init__``)."""
    path = os.environ.get("REPRO_TRACE")
    if not path or _STATE is not None:
        return
    import atexit
    start_tracing(path)
    atexit.register(stop_tracing)
