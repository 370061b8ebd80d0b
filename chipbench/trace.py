"""The profiler trace of a run, reduced to device and host figures.

``capture`` runs a block under ``jax.profiler`` and returns the path of
the ``.xplane.pb`` it wrote.  ``events`` reads one into plain tuples;
``reduce_events`` turns them into what the metrics read:

* device ops: the events of each ``/device:TPU:<i>`` plane's "XLA Ops"
  line, named by their HLO instruction (``%paramspmm_v2_k56_r32_d128.3
  = ...`` is the op ``paramspmm_v2_k56_r32_d128``);
* busy seconds: the union of those intervals, averaged over the chips;
* idle gaps: the holes in that union, each named by the benchmark's own
  host span (``step``, ``serve.submit``, ``serve.tick``, written with
  ``TraceAnnotation``) that covers most of it, else ``host``.

Device and host events share one clock in the trace.
"""
from __future__ import annotations

import contextlib
import glob
import os
import re
from collections import defaultdict
from typing import NamedTuple

HOST_SPANS = ("step", "serve.submit", "serve.tick", "serve.wait")
_OP = re.compile(r"^%?([^\s=]+?)(?:\.\d+)?(?:\s*=.*)?$", re.S)


class Event(NamedTuple):
    where: str      # "device:<i>" or "host"
    name: str
    start_ns: float
    dur_ns: float


def op_name(hlo_text: str) -> str:
    """``%fusion.24 = f32[...] fusion(...)`` → ``fusion``."""
    m = _OP.match(hlo_text.strip())
    return m.group(1) if m else hlo_text


@contextlib.contextmanager
def capture(out_dir: str):
    """Profile the body; yields a list that holds the trace path after."""
    import jax
    os.makedirs(out_dir, exist_ok=True)
    found: list = []
    jax.profiler.start_trace(out_dir)
    try:
        yield found
    finally:
        jax.profiler.stop_trace()
        paths = sorted(glob.glob(os.path.join(
            out_dir, "plugins", "profile", "*", "*.xplane.pb")),
            key=os.path.getmtime)
        if paths:
            found.append(paths[-1])


def events(xplane_path: str) -> list:
    """Device ops and the benchmark's host spans of one trace."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(xplane_path)
    out = []
    for plane in data.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    out.extend(Event(f"device:{m.group(1)}",
                                     op_name(e.name), e.start_ns,
                                     e.duration_ns) for e in line.events)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                out.extend(Event("host", e.name, e.start_ns, e.duration_ns)
                           for e in line.events if e.name in HOST_SPANS)
    return out


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def reduce_events(evs: list, window: tuple | None = None,
                  top: int = 10) -> dict:
    """Kernel time by op name, busy and window seconds, and idle gaps.

    ``window`` is ``(start_ns, end_ns)`` on the trace clock; by default
    the span from the first to the last host span or device op.  Device
    ops are clipped to it.  Returns ``op_seconds`` {name: seconds summed
    over chips}, ``busy_s`` (mean over chips), ``window_s``, ``chips``
    and the ``top`` longest idle ``gaps`` [(host span, seconds)]."""
    dev = [e for e in evs if e.where.startswith("device:")]
    host = [e for e in evs if e.where == "host"]
    if window is None:
        pts = [(e.start_ns, e.start_ns + e.dur_ns) for e in host or dev]
        window = (min(p[0] for p in pts), max(p[1] for p in pts))
    w0, w1 = window
    op_s: dict = defaultdict(float)
    per_chip: dict = defaultdict(list)
    for e in dev:
        s, t = max(e.start_ns, w0), min(e.start_ns + e.dur_ns, w1)
        if t > s:
            op_s[e.name] += (t - s) * 1e-9
            per_chip[e.where].append((s, t))
    chips = max(1, len(per_chip))
    busy = {c: _union(iv) for c, iv in per_chip.items()}
    busy_s = sum((t - s) for u in busy.values() for s, t in u) * 1e-9 / chips
    gaps = []
    for u in busy.values():
        edges = [w0] + [x for st in u for x in st] + [w1]
        for s, t in zip(edges[::2], edges[1::2]):
            if t > s:
                gaps.append((s, t))
    gaps.sort(key=lambda g: g[0] - g[1])
    gaps = [(_cover(host, s, t), (t - s) * 1e-9) for s, t in gaps[:top]]
    return {"op_seconds": dict(op_s), "busy_s": busy_s,
            "window_s": (w1 - w0) * 1e-9, "chips": chips, "gaps": gaps}


def _cover(host, s, t) -> str:
    """The host span overlapping ``[s, t)`` the most, else ``host``."""
    best, name = 0.0, "host"
    for e in host:
        o = min(t, e.start_ns + e.dur_ns) - max(s, e.start_ns)
        if o > best:
            best, name = o, e.name
    return name


def seconds_by_prefix(op_seconds: dict, prefix: str) -> float:
    """Device seconds of every op whose name starts with ``prefix``."""
    return sum(v for k, v in op_seconds.items() if k.startswith(prefix))


def breakdown(red: dict, top: int = 10) -> dict:
    """The result line's ``breakdown``: the ops that took the most device time
    and the longest idle gaps, each with what the host was doing."""
    ops = sorted(red["op_seconds"].items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in red["gaps"][:top]]}


def idle_share(red: dict | None) -> float | None:
    """Percent of the window with no device op running (None without a
    trace or a window)."""
    if not red or red["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
