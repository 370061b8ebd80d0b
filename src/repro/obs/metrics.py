"""Labeled counter/gauge/histogram registry for the obs layer.

Instruments are cheap named handles — ``counter("pack_cache_hits_total")``
returns the same object every call.  Every mutating method
(``inc``/``set``/``observe``) records whether or not a tracing session
is open: each is a lock and a dict update, placed at layer boundaries
and never inside a jitted step.  ``start_tracing`` resets the registry,
so a session's snapshot covers its own window.  Label sets distinguish
series within one instrument (``inc(kind="steering")``);
``metrics_snapshot()`` renders everything into plain JSON-ready dicts
keyed ``"k=v,k2=v2"``.

The module also owns the one Pallas launch-count definition:
``intercept_pallas(callback)`` patches ``pl.pallas_call`` so each
dispatch reports ``kw.get("name", "?")`` — trace-time count == launch
count per call.  ``benchmarks.common.count_pallas_calls`` and the fusion
tests both count through it.
"""
from __future__ import annotations

import threading
from contextlib import contextmanager
from typing import Callable

__all__ = [
    "counter", "gauge", "histogram",
    "metrics_snapshot", "reset_metrics", "intercept_pallas",
]

_LOCK = threading.Lock()
_REGISTRY: dict[str, "_Instrument"] = {}


def _label_key(labels: dict) -> str:
    return ",".join(f"{k}={v}" for k, v in sorted(labels.items()))


class _Instrument:
    kind = "?"

    def __init__(self, name: str):
        self.name = name
        self._series: dict[str, object] = {}

    def _reset(self):
        self._series = {}


class Counter(_Instrument):
    """Monotonically increasing per-label-set totals."""

    kind = "counter"

    def inc(self, value: float = 1.0, **labels) -> None:
        key = _label_key(labels)
        with _LOCK:
            self._series[key] = self._series.get(key, 0.0) + value


class Gauge(_Instrument):
    """Last-write-wins per-label-set values."""

    kind = "gauge"

    def set(self, value: float, **labels) -> None:
        with _LOCK:
            self._series[_label_key(labels)] = float(value)


class Histogram(_Instrument):
    """Streaming count/sum/min/max per label set (no buckets — the
    trace spans carry the full distribution when one is needed)."""

    kind = "histogram"

    def observe(self, value: float, **labels) -> None:
        value = float(value)
        key = _label_key(labels)
        with _LOCK:
            st = self._series.get(key)
            if st is None:
                self._series[key] = {"count": 1, "sum": value,
                                     "min": value, "max": value}
            else:
                st["count"] += 1
                st["sum"] += value
                st["min"] = min(st["min"], value)
                st["max"] = max(st["max"], value)


def _get(name: str, cls) -> _Instrument:
    with _LOCK:
        inst = _REGISTRY.get(name)
        if inst is None:
            inst = _REGISTRY[name] = cls(name)
        elif not isinstance(inst, cls):
            raise TypeError(f"metric {name!r} already registered as "
                            f"{inst.kind}, not {cls.kind}")
        return inst


def counter(name: str) -> Counter:
    """Get-or-create the named counter."""
    return _get(name, Counter)


def gauge(name: str) -> Gauge:
    """Get-or-create the named gauge."""
    return _get(name, Gauge)


def histogram(name: str) -> Histogram:
    """Get-or-create the named histogram."""
    return _get(name, Histogram)


def _fold_pending_stalls() -> None:
    """Fold in the GC records queued where taking ``_LOCK`` is unsafe
    (``repro.obs.stalls``), so a read or reset sees them."""
    from repro.obs import stalls
    stalls._drain()


def metrics_snapshot() -> dict:
    """``{metric_name: {"k=v,...": value_or_stats}}`` for every series
    with at least one observation (JSON-ready)."""
    _fold_pending_stalls()
    with _LOCK:
        return {name: {k: (dict(v) if isinstance(v, dict) else v)
                       for k, v in inst._series.items()}
                for name, inst in _REGISTRY.items() if inst._series}


def reset_metrics() -> None:
    """Zero every series (instruments stay registered)."""
    _fold_pending_stalls()
    with _LOCK:
        for inst in _REGISTRY.values():
            inst._reset()


# ------------------------------------------------- pallas interception
@contextmanager
def intercept_pallas(callback: Callable[[str], None]):
    """Patch ``pl.pallas_call`` for the body; ``callback(kernel_name)``
    fires per dispatch.  THE shared launch-count definition —
    ``count_pallas_calls`` and the fusion tests route through here."""
    from jax.experimental import pallas as pl
    orig = pl.pallas_call

    def counting(*a, **kw):
        callback(kw.get("name", "?"))
        return orig(*a, **kw)

    pl.pallas_call = counting
    try:
        yield
    finally:
        pl.pallas_call = orig
