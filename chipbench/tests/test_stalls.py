"""Host-stall records placed on a recorded TPU v5e profile.

``data/gcn_probe.xplane.pb`` (see ``test_trace.py``) starts at epoch
``profile_start_time``; hand-made records are placed through it, as the
program's ``time.time_ns()`` records are in a run.
"""
import os
from types import SimpleNamespace

import pytest

from chipbench import harness, stalls, trace

PROBE = os.path.join(os.path.dirname(__file__), "data",
                     "gcn_probe.xplane.pb")
READERS = ("compile_s", "spmm_build_s", "device_idle_stalled.train")


def rec(kind, start_ns, end_ns):
    """A program record; its times are whole epoch nanoseconds."""
    return SimpleNamespace(kind=kind, what="f", start_ns=round(start_ns),
                           end_ns=round(end_ns), cache=None)


@pytest.fixture(scope="module")
def prof():
    p = stalls.profile(PROBE)
    assert p is not None
    return p


@pytest.fixture(scope="module")
def longest_gap(prof):
    """The longest idle stretch of the window, as offsets."""
    w0, w1 = prof.window
    busy = trace._union([(max(e.start_ns, w0), min(e.start_ns + e.dur_ns, w1))
                         for e in prof.events if e.where == "device:0"
                         and e.start_ns + e.dur_ns > w0 and e.start_ns < w1])
    edges = [w0] + [x for st in busy for x in st] + [w1]
    return max(zip(edges[::2], edges[1::2]), key=lambda g: g[1] - g[0])


def test_profile_clock_and_window(prof):
    assert prof.start_ns == 1792188764217912251
    assert prof.stop_ns > prof.start_ns
    steps = [e for e in prof.events if e.name == "step"]
    assert steps[0].start_ns == pytest.approx(46.7e6, abs=0.1e6)
    red = trace.reduce_events(prof.events)
    assert prof.window[1] - prof.window[0] == pytest.approx(
        red["window_s"] * 1e9)


def _read(monkeypatch, records, xplane=PROBE):
    monkeypatch.setattr(stalls, "latest_xplane", lambda: xplane)
    monkeypatch.setattr(stalls, "program_stalls", lambda: records)
    ctx = {"trace": {"window_s": 1.0}}
    return {m: harness.reader(m)(ctx) for m in READERS}


def test_compile_over_a_gap_counts_as_stalled(monkeypatch, prof,
                                              longest_gap):
    s, t = longest_gap
    assert t - s > 10e6                # the 10 ms sleep under serve.tick
    t0 = prof.start_ns
    got = _read(monkeypatch, [rec("compile.backend", t0 + round(s),
                                  t0 + round(t))])
    w = prof.window[1] - prof.window[0]
    assert got["device_idle_stalled.train"] == pytest.approx(
        100.0 * (t - s) / w, abs=1e-6)
    red = trace.reduce_events(prof.events)
    assert got["device_idle_stalled.train"] <= trace.idle_share(red)
    assert got["compile_s"] == 0.0     # it ends after the profile starts


def test_record_over_busy_time_is_not_idle(monkeypatch, prof):
    dev = next(e for e in prof.events if e.where == "device:0"
               and e.name.startswith("paramspmm_"))
    t0 = prof.start_ns
    got = _read(monkeypatch, [rec("gc", t0 + round(dev.start_ns),
                                  t0 + round(dev.start_ns + dev.dur_ns))])
    assert got["device_idle_stalled.train"] == pytest.approx(0.0, abs=1e-6)


def test_record_before_the_window_counts_in_compile_s(monkeypatch, prof):
    t0 = prof.start_ns
    recs = [rec("compile.trace", t0 - 5_000_000_000, t0 - 4_000_000_000),
            rec("compile.lower", t0 - 3_000_000_000, t0 - 1_000_000_000),
            # nested inside the one before: counted once
            rec("compile.trace", t0 - 2_000_000_000, t0 - 1_500_000_000),
            rec("gc", t0 - 900_000_000, t0 - 800_000_000)]
    got = _read(monkeypatch, recs)
    assert got["compile_s"] == pytest.approx(3.0)
    assert got["device_idle_stalled.train"] == 0.0


def test_record_after_the_profile_counts_in_neither(monkeypatch, prof):
    stop = prof.stop_ns
    got = _read(monkeypatch, [rec("compile.backend", stop + 1_000,
                                  stop + 2_000_000_000),
                              rec("gc", stop + 10, stop + 5_000_000)])
    assert got["compile_s"] == 0.0
    assert got["device_idle_stalled.train"] == 0.0


def test_readers_give_none_without_a_tpu_plane(monkeypatch, tmp_path):
    import jax
    import jax.numpy as jnp

    with trace.capture(str(tmp_path)) as found:
        jax.jit(lambda x: x + 1)(jnp.ones(4)).block_until_ready()
    assert found and stalls.profile(found[0]) is None
    got = _read(monkeypatch, [rec("compile.trace", 0, 1)], found[0])
    assert got == dict.fromkeys(READERS)


def test_readers_give_none_without_a_trace_or_records(monkeypatch):
    monkeypatch.setattr(stalls, "latest_xplane", lambda: PROBE)
    for m in READERS:
        assert harness.reader(m)({"trace": None}) is None
    monkeypatch.setattr(stalls, "program_stalls", lambda: None)
    for m in READERS:
        assert harness.reader(m)({"trace": {"window_s": 1.0}}) is None


def test_spmm_build_s_sums_the_program_histogram(monkeypatch):
    from repro import obs
    obs.reset_metrics()
    obs.histogram("spmm_build_seconds").observe(0.25, op="gat")
    obs.histogram("spmm_build_seconds").observe(0.5, op="spmm")
    got = _read(monkeypatch, [])
    assert got["spmm_build_s"] == pytest.approx(0.75)
    obs.reset_metrics()
