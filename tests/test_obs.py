"""Runtime telemetry layer (repro.obs): span nesting + Chrome-trace
schema, counter/label semantics, the host-stall record (compiles and GC
pauses on the wall clock), the structured decision log, drift
advisories, and the session-off guarantees (no events or decisions
recorded, aggregates kept, traced and untraced training bit-identical)."""
import gc
import json
import time

import numpy as np
import pytest

from repro import obs
from repro.obs import decisions as obs_decisions
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.core.cost_model import CostModel
from repro.core.pcsr import SpMMConfig, build_pcsr, config_space
from repro.core.sparse import CSRMatrix

from conftest import random_csr


@pytest.fixture(autouse=True)
def _clean_obs():
    """Every test starts and ends untraced with empty registries — a
    failing test must not leak an active session into the next one."""
    if obs.trace_enabled():                            # pragma: no cover
        obs.stop_tracing()
    obs.reset_metrics()
    obs.clear_decisions()
    yield
    if obs.trace_enabled():
        obs.stop_tracing()
    obs.reset_metrics()
    obs.clear_decisions()


# ------------------------------------------------------- disabled mode
def test_disabled_mode_records_nothing():
    """With no session open, spans, instants and decisions leave nothing
    behind, while counters, gauges and histograms keep recording."""
    assert not obs.trace_enabled()
    with obs.span("work", step=1):
        obs.instant("tick")
    obs.counter("c_test").inc()
    obs.gauge("g_test").set(3.0)
    obs.histogram("h_test").observe(1.0)
    assert obs.trace_events() == []
    assert obs_decisions.record_decision(
        source="cost_model", dim=32, chosen=(8, 1, 1, False, False)) is None
    assert obs.decision_log() == []
    snap = obs.metrics_snapshot()
    assert snap["c_test"] == {"": 1.0}
    assert snap["g_test"] == {"": 3.0}
    assert snap["h_test"] == {"": {"count": 1, "sum": 1.0,
                                   "min": 1.0, "max": 1.0}}


def test_disabled_span_is_the_shared_null_singleton():
    # the near-zero-overhead contract: no allocation per disabled span
    assert obs.span("a") is obs.span("b", x=1)


# ------------------------------------------- spans + chrome-trace export
def test_span_nesting_and_chrome_trace_schema(tmp_path):
    path = tmp_path / "t.json"
    with obs.tracing(str(path)):
        with obs.span("outer", kind="demo"):
            with obs.span("inner"):
                obs.instant("mark", note="hi")
        obs.counter("c_events").inc(2.0, phase="x")
    payload = json.loads(path.read_text())
    evs = payload["traceEvents"]

    complete = {e["name"]: e for e in evs if e["ph"] == "X"}
    assert set(complete) >= {"outer", "inner"}
    for e in complete.values():        # chrome "X" schema
        assert {"name", "cat", "ph", "ts", "dur", "pid", "tid"} <= set(e)
        assert e["dur"] >= 0
    # nesting by containment: inner's interval lies inside outer's
    o, i = complete["outer"], complete["inner"]
    assert o["ts"] <= i["ts"]
    assert i["ts"] + i["dur"] <= o["ts"] + o["dur"] + 1e-6
    assert o["args"]["kind"] == "demo"

    inst = [e for e in evs if e["ph"] == "i" and e["name"] == "mark"]
    assert inst and inst[0]["s"] == "t" and inst[0]["args"]["note"] == "hi"
    # one final "C" counter event per series so Perfetto renders totals
    cnt = [e for e in evs if e["ph"] == "C" and "c_events" in e["name"]]
    assert cnt and cnt[0]["args"]["value"] == 2.0
    assert payload["repro_metrics"]["c_events"] == {"phase=x": 2.0}


def test_nested_start_tracing_raises():
    obs.start_tracing()
    with pytest.raises(RuntimeError, match="already active"):
        obs.start_tracing()
    obs.stop_tracing()


def test_tracing_session_is_its_own_window(tmp_path):
    with obs.tracing():
        obs.counter("c_window").inc(5.0)
        obs_decisions.record_decision(
            source="cost_model", dim=16, chosen=(8, 1, 1, False, False))
    assert len(obs.decision_log()) == 1     # decisions survive the stop
    with obs.tracing():                     # ... until the next session
        assert obs.metrics_snapshot() == {}
        assert obs.decision_log() == []


# ------------------------------------------------------------- metrics
def test_counter_label_semantics():
    with obs.tracing():
        c = obs.counter("c_lbl")
        c.inc(a="1", b="2")
        c.inc(2.0, b="2", a="1")            # kw order must not matter
        c.inc(a="1", b="3")                 # distinct series
        c.inc()                             # unlabeled series
        snap = obs.metrics_snapshot()["c_lbl"]
    assert snap == {"a=1,b=2": 3.0, "a=1,b=3": 1.0, "": 1.0}


def test_gauge_and_histogram_semantics():
    with obs.tracing():
        obs.gauge("g_sem").set(1.0, shard=0)
        obs.gauge("g_sem").set(4.0, shard=0)        # last write wins
        h = obs.histogram("h_sem")
        for v in (1.0, 3.0, 2.0):
            h.observe(v)
        snap = obs.metrics_snapshot()
    assert snap["g_sem"] == {"shard=0": 4.0}
    assert snap["h_sem"][""] == {"count": 3, "sum": 6.0,
                                 "min": 1.0, "max": 3.0}


def test_metric_kind_mismatch_raises():
    obs.counter("m_kind")
    with pytest.raises(TypeError, match="counter"):
        obs.gauge("m_kind")


def _compiles(phase):
    series = obs.metrics_snapshot().get("compiles_total", {})
    return sum(v for k, v in series.items() if f"phase={phase}" in k)


def test_pallas_probe_counts_launches(rng):
    """A kernel traced during the session shows up in compiles_total
    once; calling it again compiles nothing."""
    from repro.kernels.paramspmm.ops import paramspmm

    csr, _ = random_csr(rng, 43, 0.2)      # fresh shape → no jit cache hit
    p = build_pcsr(csr.indptr, csr.indices, csr.data, 43, 43,
                   SpMMConfig(V=1, S=False, W=8, F=1))
    B = np.asarray(rng.standard_normal((43, 8)), np.float32)
    with obs.tracing():
        paramspmm(p, B, interpret=True)
        traced, built = _compiles("trace"), _compiles("backend")
        assert traced >= 1 and built >= 1, obs.metrics_snapshot().keys()
        paramspmm(p, B, interpret=True)
        assert (_compiles("trace"), _compiles("backend")) == (traced, built)


# --------------------------------------------------------- host stalls
def _stalls_since(t0_ns):
    return [r for r in obs.host_stalls() if r.start_ns >= t0_ns]


def test_fresh_jit_records_each_compile_phase_on_the_wall_clock():
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.tanh(x) * 3.0 - x)
    x = jnp.ones((7, 13), jnp.float32)
    t0 = time.time_ns()
    f(x).block_until_ready()
    t1 = time.time_ns()
    recs = [r for r in _stalls_since(t0) if r.kind != "gc"]
    mine = [r for r in recs if "<lambda>" in r.what]
    assert {r.kind for r in mine} == {"compile.trace", "compile.lower",
                                      "compile.backend"}
    for r in recs:
        assert t0 <= r.start_ns <= r.end_ns <= t1, r
    assert all(r.cache is None for r in recs if r.kind != "compile.backend")
    # the aggregates saw them with no session open
    assert _compiles("trace") >= 1 and _compiles("backend") >= 1
    secs = obs.metrics_snapshot()["compile_seconds"]
    assert secs["phase=backend"]["count"] >= 1
    # a steady call compiles nothing
    t2 = time.time_ns()
    f(x).block_until_ready()
    assert [r for r in _stalls_since(t2) if r.kind != "gc"] == []


def test_gc_collect_adds_a_gc_record():
    t0 = time.time_ns()
    gc.collect()
    t1 = time.time_ns()
    full = [r for r in _stalls_since(t0) if r.what == "gen2"]
    assert full and full[0].kind == "gc"
    assert t0 <= full[0].start_ns <= full[0].end_ns <= t1
    pauses = obs.metrics_snapshot()["gc_pause_seconds"][""]
    assert pauses["count"] >= 1 and pauses["sum"] > 0


def test_gc_inside_a_metric_update_neither_deadlocks_nor_loses_counts(
        monkeypatch):
    """Collections forced while threads hold the metrics lock (a GC
    callback runs inside whatever allocation triggered it) must not
    deadlock, and concurrent increments must not be lost."""
    import sys
    import threading

    from repro.obs import stalls as obs_stalls

    monkeypatch.setattr(obs_stalls, "GC_MIN_NS", 0)    # record every pass
    n_threads, n_ops = 12, 3000
    t0 = time.time_ns()

    def work(i):
        c, h = obs.counter("c_stress"), obs.histogram("h_stress")
        for k in range(n_ops):
            c.inc(worker=i % 3)
            h.observe(float(k), worker=i % 3)
            if k % 500 == 0:
                obs.metrics_snapshot()

    threshold, interval = gc.get_threshold(), sys.getswitchinterval()
    gc.set_threshold(5, 1, 1)          # a collection every few allocations
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(i,), daemon=True)
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        deadline = time.monotonic() + 120
        for t in threads:
            t.join(timeout=max(0.0, deadline - time.monotonic()))
        assert not any(t.is_alive() for t in threads), "deadlocked"
    finally:
        gc.set_threshold(*threshold)
        sys.setswitchinterval(interval)
    snap = obs.metrics_snapshot()
    assert sum(snap["c_stress"].values()) == n_threads * n_ops
    assert sum(v["count"] for v in snap["h_stress"].values()) == \
        n_threads * n_ops
    assert [r for r in _stalls_since(t0) if r.kind == "gc"]
    assert snap["gc_pause_seconds"][""]["count"] >= 1


def test_param_spmm_build_observed_without_a_session(rng):
    from repro.core.engine import make_gat_message_fn
    from repro.pipeline import ParamSpMM

    csr, _ = random_csr(rng, 40, 0.15)
    assert not obs.trace_enabled()
    ParamSpMM(csr, 8, backend="engine")
    p = ParamSpMM(csr, 8, op="gat", heads=2, backend="engine")
    make_gat_message_fn(p.op.pcsr, p.op.pcsr_t, backend="engine")
    snap = obs.metrics_snapshot()["spmm_build_seconds"]
    assert set(snap) == {"op=spmm", "op=gat", "op=gat_message"}
    assert all(v["count"] == 1 and v["sum"] > 0 for v in snap.values())
    assert obs.trace_events() == []


def test_spmm_build_span_in_a_session(rng):
    from repro.pipeline import ParamSpMM

    csr, _ = random_csr(rng, 40, 0.15)
    with obs.tracing():
        ParamSpMM(csr, 8, backend="engine")
        spans = [e for e in obs.trace_events() if e["name"] == "spmm.build"]
    assert len(spans) == 1 and spans[0]["args"]["op"] == "spmm"
    assert spans[0]["args"]["nnz"] == csr.nnz


def test_exported_trace_carries_its_wall_clock_start(tmp_path):
    path = tmp_path / "t.json"
    t0 = time.time_ns()
    with obs.tracing(str(path)):
        with obs.span("s"):
            pass
    t1 = time.time_ns()
    payload = json.loads(path.read_text())
    start = payload["otherData"]["t0_ns"]
    assert t0 <= start <= t1
    [ev] = [e for e in payload["traceEvents"] if e["name"] == "s"]
    assert t0 <= start + ev["ts"] * 1e3 <= t1


# -------------------------------------------------------- decision log
def test_cost_model_best_records_decision(rng, tmp_path):
    csr, _ = random_csr(rng, 64, 0.1)
    path = tmp_path / "d.json"
    with obs.tracing(str(path)):
        cfg, _ = CostModel(csr).best(32, config_space(32))
    log = obs.decision_log()
    assert len(log) == 1
    rec = log[0]
    assert rec.source == "cost_model" and rec.op == "spmm"
    assert rec.dim == 32 and rec.chosen == tuple(cfg.astuple())
    assert rec.calibration is None          # analytic constants
    # top-k candidates sorted cheapest-first, chosen == cheapest
    secs = [c["seconds"] for c in rec.topk]
    assert secs == sorted(secs) and len(secs) >= 2
    assert tuple(rec.topk[0]["config"]) == rec.chosen
    assert rec.predicted_seconds == pytest.approx(secs[0])
    for name in obs_decisions.DRIFT_FEATURES:
        assert name in rec.snapshot
    # round-trip through the exported trace
    payload = json.loads(path.read_text())
    [d] = payload["repro_decisions"]
    assert d["chosen"] == list(rec.chosen)
    assert d["snapshot"]["nnz"] == rec.snapshot["nnz"]
    assert payload["repro_metrics"]["decisions_total"] == {
        "op=spmm,source=cost_model": 1.0}


def test_record_decision_scores_rank_highest_first():
    space = [(8, 1, 1, False, False), (8, 2, 1, False, False),
             (16, 1, 2, True, False)]
    with obs.tracing():
        rec = obs_decisions.record_decision(
            source="decider", dim=64, chosen=space[1],
            scores=zip(space, [0.2, 0.7, 0.1]), snapshot={"n": 1.0}, k=2)
    assert [c["score"] for c in rec.topk] == [0.7, 0.2]
    assert tuple(rec.topk[0]["config"]) == space[1]


# ----------------------------------------------------- drift advisories
def _densified(csr, rng):
    A = csr.to_dense()
    extra = (rng.random(A.shape) < 0.3).astype(np.float32)
    return CSRMatrix.from_dense(A + extra)


def test_drift_advisory_fires_on_mutated_graph_only(rng):
    csr, _ = random_csr(rng, 64, 0.05)
    with obs.tracing():
        CostModel(csr).best(32, config_space(32))
    # post-trace: same graph → quiet
    assert obs_decisions.check_drift(csr) is None
    # densified graph → advisory naming the moved features + the pick
    adv = obs_decisions.check_drift(_densified(csr, rng))
    assert adv is not None and "nnz" in adv.drifted
    assert adv.drifted["nnz"]["rel"] > obs_decisions.DRIFT_THRESHOLD
    assert str(adv.record.chosen) in adv.message
    assert "re-run config selection" in adv.message


def test_check_drift_without_decisions_raises(rng):
    csr, _ = random_csr(rng, 32, 0.1)
    with pytest.raises(ValueError, match="no decision"):
        obs_decisions.check_drift(csr)


# ------------------------------------- traced == untraced (gnn training)
def test_traced_training_matches_untraced(tmp_path):
    from repro.apps.gnn import train_gnn
    from repro.data.tasks import community_task

    task = community_task(n_blocks=4, block_size=32, feat_dim=8,
                          p_in=0.3, seed=0)
    kw = dict(model="gcn", hidden=16, n_layers=2, steps=4, seed=0)
    base = train_gnn(task, **kw)
    path = tmp_path / "gnn.json"
    with obs.tracing(str(path)):
        traced = train_gnn(task, **kw)
    # observability must not perturb the computation
    np.testing.assert_array_equal(np.asarray(base.losses),
                                  np.asarray(traced.losses))
    payload = json.loads(path.read_text())
    names = {e["name"] for e in payload["traceEvents"] if e["ph"] == "X"}
    assert {"gnn.pack", "gnn.compile", "gnn.step"} <= names
    hits = payload["repro_metrics"].get("pack_cache_hits_total", {})
    assert sum(hits.values()) >= 1          # steering cache observed
    assert payload["repro_decisions"]        # config pick logged


# ----------------------------------------------------------- obs_report
def test_obs_report_summarizes_trace(tmp_path, capsys, rng):
    from repro.apps import obs_report

    csr, _ = random_csr(rng, 48, 0.1)
    path = tmp_path / "r.json"
    with obs.tracing(str(path)):
        with obs.span("outer"):
            with obs.span("inner"):
                CostModel(csr).best(16, config_space(16))
    assert obs_report.main([str(path)]) == 0
    out = capsys.readouterr().out
    assert "outer" in out and "inner" in out
    assert "cost_model" in out               # decision summary
    assert "decisions_total" in out          # counter section


def test_obs_report_rejects_non_trace_file(tmp_path, capsys):
    from repro.apps import obs_report

    bad = tmp_path / "bad.json"
    bad.write_text("{\"rows\": []}")
    assert obs_report.main([str(bad)]) == 1
    missing = tmp_path / "nope.json"
    assert obs_report.main([str(missing)]) == 1


# -------------------------------------------------------- env autostart
def test_env_autostart(tmp_path, monkeypatch):
    path = tmp_path / "env.json"
    monkeypatch.setenv("REPRO_TRACE", str(path))
    obs_trace._env_autostart()
    assert obs.trace_enabled()
    obs.instant("from_env")
    assert obs.stop_tracing() == str(path)   # atexit re-run is a no-op
    payload = json.loads(path.read_text())
    assert any(e["name"] == "from_env" for e in payload["traceEvents"])


# ------------------------------------------- configurable drift thresholds
def test_resolve_drift_thresholds_scalar_dict_env(monkeypatch):
    r = obs_decisions.resolve_drift_thresholds
    monkeypatch.delenv(obs_decisions.DRIFT_THRESHOLD_ENV, raising=False)
    # default: every feature at DRIFT_THRESHOLD
    assert r() == {f: obs_decisions.DRIFT_THRESHOLD
                   for f in obs_decisions.DRIFT_FEATURES}
    # scalar broadcast
    assert r(0.5) == {f: 0.5 for f in obs_decisions.DRIFT_FEATURES}
    # partial dict overrides ride on the default base
    t = r({"nnz": 0.05, "cv": 2.0})
    assert t["nnz"] == 0.05 and t["cv"] == 2.0
    assert t["d_max"] == obs_decisions.DRIFT_THRESHOLD
    with pytest.raises(ValueError, match="unknown drift feature"):
        r({"not_a_feature": 0.1})
    # env hook: scalar form, then per-feature list form
    monkeypatch.setenv(obs_decisions.DRIFT_THRESHOLD_ENV, "0.4")
    assert r()["nnz"] == 0.4
    monkeypatch.setenv(obs_decisions.DRIFT_THRESHOLD_ENV,
                       "nnz=0.02, cv=1.5")
    t = r()
    assert t["nnz"] == 0.02 and t["cv"] == 1.5
    assert t["rho"] == obs_decisions.DRIFT_THRESHOLD
    # an explicit argument beats the env
    assert r(0.9)["nnz"] == 0.9


def test_check_drift_per_feature_threshold_and_advisory_record(rng):
    csr, _ = random_csr(rng, 64, 0.05)
    with obs.tracing():
        CostModel(csr).best(32, config_space(32))
    mutated = _densified(csr, rng)
    # a sky-high nnz threshold silences the nnz advisory dimension
    loose = obs_decisions.check_drift(mutated, threshold={"nnz": 100.0})
    assert loose is None or "nnz" not in loose.drifted
    # a tight one fires, and the advisory records WHICH threshold fired
    adv = obs_decisions.check_drift(mutated, threshold={"nnz": 0.01})
    assert adv is not None and "nnz" in adv.drifted
    assert adv.drifted["nnz"]["threshold"] == 0.01
    assert "1%" in adv.message                 # the fired bound, printed
