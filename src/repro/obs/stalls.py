"""Host stalls: JAX compiles and Python GC pauses, always recorded.

Installed once, when ``repro.obs`` is imported, and on whether or not a
tracing session is open:

* a ``jax.monitoring`` time-span listener records JAX's three compile
  phases — ``compile.trace`` (jaxpr tracing), ``compile.lower`` (jaxpr to
  MLIR) and ``compile.backend`` (XLA compile, or a load from the
  persistent compilation cache, which that phase wraps).  A backend
  record's ``cache`` says which: ``"hit"`` (loaded), ``"miss"``
  (compiled and written to the cache) or ``None`` (compiled with no
  cache entry read or written);
* a ``gc.callbacks`` hook records every generation-2 collection and any
  younger one of ``GC_MIN_NS`` or more.

Records appear only when JAX compiles or Python collects; a steady
jitted step adds none.  Their times are ``time.time_ns()`` wall-clock
nanoseconds, the clock a JAX profiler trace is anchored to: subtracting
the trace's ``profile_start_time`` places a record on the device trace.
``host_stalls()`` returns the newest ``CAPACITY`` of them; each older one
pushed out counts in ``host_stalls_dropped_total``.  Aggregates:
``compiles_total{phase,cache}``, ``compile_seconds{phase}`` and
``gc_pause_seconds``.

A GC callback can fire inside any allocation, including one made while
this thread holds the metrics lock, so the hook only appends to a
lock-free deque; the records are folded in by the next compile, read of
``host_stalls()`` or metrics snapshot.
"""
from __future__ import annotations

import gc
import threading
from collections import deque
from time import time_ns
from typing import NamedTuple, Optional

from repro.obs import metrics as _metrics

__all__ = ["HostStall", "host_stalls"]

#: Records kept per process; past it the oldest are dropped and counted.
CAPACITY = 50_000
#: Shortest generation-0/1 collection recorded (every generation-2 one is).
GC_MIN_NS = 1_000_000

_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}


class HostStall(NamedTuple):
    """One interval in which the host compiled or collected garbage."""

    kind: str               # compile.{trace,lower,backend} | gc
    what: str               # the compiled function's name, or "gen<i>"
    start_ns: int           # time.time_ns() wall clock
    end_ns: int
    cache: Optional[str]    # compile.backend: "hit" | "miss" | None


_PENDING: deque = deque()           # appended lock-free, folded by _drain
_RECORDS: deque = deque(maxlen=CAPACITY)
_DRAIN_LOCK = threading.Lock()
#: The persistent-cache event seen by this thread's running backend compile.
_CACHE_FLAG = threading.local()
_GC_START = [0]                     # time_ns() of the running collection


def _drain() -> None:
    """Move pending records into the kept list and the aggregates."""
    if not _PENDING:
        return
    with _DRAIN_LOCK:
        while _PENDING:
            rec = _PENDING.popleft()
            if len(_RECORDS) == CAPACITY:
                _metrics.counter("host_stalls_dropped_total").inc()
            _RECORDS.append(rec)
            seconds = (rec.end_ns - rec.start_ns) * 1e-9
            if rec.kind == "gc":
                _metrics.histogram("gc_pause_seconds").observe(seconds)
            else:
                phase = rec.kind[len("compile."):]
                _metrics.counter("compiles_total").inc(
                    phase=phase, cache=rec.cache or "none")
                _metrics.histogram("compile_seconds").observe(
                    seconds, phase=phase)


def host_stalls() -> list[HostStall]:
    """The kept compile and GC records of this process, oldest first."""
    _drain()
    with _DRAIN_LOCK:
        return list(_RECORDS)


def _on_event(event: str, **kwargs) -> None:
    flag = _CACHE_EVENTS.get(event)
    if flag is not None:
        _CACHE_FLAG.value = flag


def _on_time_span(event: str, start_s: float, end_s: float,
                  **kwargs) -> None:
    phase = _PHASES.get(event)
    if phase is None:
        return
    cache = None
    if phase == "backend":
        cache = getattr(_CACHE_FLAG, "value", None)
        _CACHE_FLAG.value = None
    _PENDING.append(HostStall(f"compile.{phase}",
                              str(kwargs.get("fun_name", "?")),
                              int(start_s * 1e9), int(end_s * 1e9), cache))
    _drain()


def _on_gc(phase: str, info: dict) -> None:
    now = time_ns()
    if phase == "start":
        _GC_START[0] = now
        return
    gen = info.get("generation", -1)
    if gen == 2 or now - _GC_START[0] >= GC_MIN_NS:
        _PENDING.append(HostStall("gc", f"gen{gen}", _GC_START[0], now,
                                  None))


def _install() -> None:
    """Register the listeners (once per process)."""
    if _on_gc in gc.callbacks:
        return
    import jax.monitoring
    jax.monitoring.register_event_listener(_on_event)
    jax.monitoring.register_event_time_span_listener(_on_time_span)
    gc.callbacks.append(_on_gc)
