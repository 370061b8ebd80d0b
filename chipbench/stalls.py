"""The program's host-stall records on the clock of the run's device trace.

The program keeps a record of every JAX compile phase and every long
Python GC pause (``repro.obs.host_stalls()``), each with its start and
end in ``time.time_ns()`` nanoseconds.  A profiler trace's ``Task
Environment`` plane holds ``profile_start_time`` and
``profile_stop_time`` on that clock, and its events' ``start_ns`` are
offsets from the start, so one subtraction places a record on the trace.

The trace is the newest ``*.xplane.pb`` under ``chipbench_out/``, where
``run.py`` points the runner; its window is the one ``trace.reduce_events``
takes by default.  The readers get ``None`` where there is nothing to
read: a run without a trace, a trace with no TPU plane, a program that
keeps no such record, or one that dropped records.
"""
from __future__ import annotations

import functools
import glob
import os
from typing import NamedTuple

from chipbench import harness, trace

COMPILE_KINDS = ("compile.trace", "compile.lower", "compile.backend")


class Profile(NamedTuple):
    start_ns: int       # profile_start_time, epoch ns
    stop_ns: int        # profile_stop_time, epoch ns
    events: list        # trace.events, offsets from start_ns
    window: tuple       # (w0, w1) offsets, as reduce_events takes it


class Stall(NamedTuple):
    kind: str
    start_ns: int       # offsets from the profile's start
    end_ns: int


def latest_xplane(root: str = harness.ROOT) -> str | None:
    paths = glob.glob(os.path.join(root, "chipbench_out", "**",
                                   "*.xplane.pb"), recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


@functools.lru_cache(maxsize=4)
def _load(path: str, mtime: float) -> Profile | None:
    from jax.profiler import ProfileData
    env = ProfileData.from_file(path).find_plane_with_name(
        "Task Environment")
    evs = trace.events(path)
    dev = [e for e in evs if e.where.startswith("device:")]
    if env is None or not dev:
        return None
    stats = {k: v for k, v in env.stats}
    host = [e for e in evs if e.where == "host"]
    pts = [(e.start_ns, e.start_ns + e.dur_ns) for e in host or dev]
    return Profile(int(stats["profile_start_time"]),
                   int(stats["profile_stop_time"]), evs,
                   (min(p[0] for p in pts), max(p[1] for p in pts)))


def profile(path: str | None) -> Profile | None:
    """The trace at ``path``, or ``None`` where it has no TPU plane."""
    if not path or not os.path.isfile(path):
        return None
    return _load(path, os.path.getmtime(path))


def program_stalls() -> list | None:
    """The program's records, or ``None`` where it keeps none (an older
    program) or dropped some."""
    try:
        from repro.obs import host_stalls, metrics_snapshot
    except ImportError:
        return None
    recs = host_stalls()
    if metrics_snapshot().get("host_stalls_dropped_total"):
        return None
    return recs


def placed(prof: Profile, records) -> list:
    """``records`` as offsets from the profile's start."""
    return [Stall(r.kind, r.start_ns - prof.start_ns,
                  r.end_ns - prof.start_ns) for r in records]


def on_trace(ctx: dict):
    """``(profile, placed records)`` of a traced run, else ``None``."""
    if not ctx.get("trace"):
        return None
    prof = profile(latest_xplane())
    if prof is None:
        return None
    recs = program_stalls()
    if recs is None:
        return None
    return prof, placed(prof, recs)


def _overlap(a: list, b: list) -> int:
    """Length of the intersection of two sorted disjoint interval lists."""
    i = j = total = 0
    while i < len(a) and j < len(b):
        s, t = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(0, t - s)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def compile_seconds(stalls: list) -> float:
    """Seconds in which the host was compiling, over the compile records
    that end before the profile starts (set-up; the reference's compiles
    after the window are left out).  Nested phases count once."""
    held = trace._union([(s.start_ns, s.end_ns) for s in stalls
                         if s.kind in COMPILE_KINDS and s.end_ns <= 0])
    return sum(t - s for s, t in held) * 1e-9


def idle_stalled_share(prof: Profile, stalls: list) -> float:
    """Percent of the window in which the chip ran nothing while a
    compile or GC record was open, averaged over the chips."""
    w0, w1 = prof.window
    open_ = trace._union([(max(s.start_ns, w0), min(s.end_ns, w1))
                          for s in stalls if min(s.end_ns, w1) >
                          max(s.start_ns, w0)])
    per_chip: dict = {}
    for e in prof.events:
        if e.where.startswith("device:"):
            s, t = max(e.start_ns, w0), min(e.start_ns + e.dur_ns, w1)
            if t > s:
                per_chip.setdefault(e.where, []).append((s, t))
    held = sum(t - s for s, t in open_)
    chips = max(1, len(per_chip))
    idle = sum(held - _overlap(open_, trace._union(iv))
               for iv in per_chip.values()) / chips
    return 100.0 * idle / (w1 - w0)
