#!/usr/bin/env bash
# Tier-1 verify: fast test tier + bytecode-compile + import/docs checks.
#   ./scripts/ci.sh              → tier-1 (slow tests deselected via pytest.ini)
#   ./scripts/ci.sh -m slow      → slow tier only
#   ./scripts/ci.sh -m "slow or not slow"  → everything
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
# the dist module runs in its own multi-device process below, not here
python -m pytest -x -q --ignore=tests/test_dist.py "$@"
# multi-device tier: the distributed subsystem needs > 1 device, which a
# CPU host only has when XLA is told to fake them — run the dist module
# in its own process so the forced device count can't leak elsewhere.
# "$@" deliberately NOT forwarded: a -k/-m/path filter aimed at the main
# run would deselect everything here (pytest exit 5 → spurious CI fail)
# or re-run arbitrary tests under the forced device count.
XLA_FLAGS="--xla_force_host_platform_device_count=8${XLA_FLAGS:+ $XLA_FLAGS}" \
    python -m pytest -x -q tests/test_dist.py \
    tests/test_chip_smoke.py::test_phase_dist_tiny
python -m compileall -q src
python scripts/check_imports.py   # every bench_*/example module imports
python scripts/check_docs.py      # README/docs symbol references resolve
# calibration smoke: the end-to-end fit CLI on a tiny design (2 graphs,
# one dim, 2 reps) incl. artifact save/reload — catches a broken fitter
# or artifact format before the full bench pass below prices with it
CAL_SMOKE="$(mktemp /tmp/calibration_smoke.XXXXXX.json)"
python -m repro.core.calibrate --fast --out "$CAL_SMOKE"
python - "$CAL_SMOKE" <<'EOF'
import sys
from repro.core.calibrate import CalibrationResult
res = CalibrationResult.load(sys.argv[1])
assert res.coef, "calibration smoke produced no coefficients"
EOF
rm -f "$CAL_SMOKE"
# obs smoke: a traced short training run must produce a Chrome-trace
# JSON the reader CLI can summarize — the acceptance path of the
# telemetry layer (spans + pack-cache counters + a logged decision)
OBS_TRACE="$(mktemp /tmp/obs_smoke.XXXXXX.json)"
python -m repro.apps.gnn --steps 2 --layers 2 --hidden 16 --trace "$OBS_TRACE" > /dev/null
python -m repro.apps.obs_report "$OBS_TRACE" --top 5
python - "$OBS_TRACE" <<'EOF'
import json, sys
t = json.load(open(sys.argv[1]))
names = {e["name"] for e in t["traceEvents"] if e["ph"] == "X"}
assert {"gnn.pack", "gnn.compile", "gnn.step"} <= names, sorted(names)
assert any("pack_cache" in m for m in t["repro_metrics"]), \
    sorted(t["repro_metrics"])
assert t["repro_decisions"], "no decision recorded in traced gnn run"
EOF
rm -f "$OBS_TRACE"
# dynamic smoke: a churn stream against a self-healing DynamicGraph must
# stay exact vs a full rebuild, surface a drift advisory, and trigger at
# least one governor re-pack — all observed through the obs counters and
# the decision log (the bounded-staleness acceptance path of the
# dynamic-graph layer, see docs/DYNAMIC.md)
python - <<'EOF'
import numpy as np
import jax.numpy as jnp
from repro import obs
from repro.core.engine import make_spmm_fn
from repro.core.pcsr import build_pcsr
from repro.data.graphs import rmat
from repro.dynamic import DynamicGraph

csr = rmat(7, 6, seed=9)
rng = np.random.default_rng(9)
with obs.tracing():
    g = DynamicGraph(csr, 16, slack=1.05, amortize_steps=10,
                     drift_threshold={"nnz": 0.05})
    for _ in range(6):
        r, c = rng.integers(0, csr.n_rows, (2, 150))
        g.insert_edges(r, c, rng.uniform(0.5, 1.5, 150).astype(np.float32))
        dcsr = g.dyn.to_csr()
        rows = np.repeat(np.arange(dcsr.n_rows), np.diff(dcsr.indptr))
        pick = rng.permutation(dcsr.nnz)[:140]
        g.delete_edges(rows[pick], dcsr.indices[pick])
    snap = obs.metrics_snapshot()
    assert sum(snap["dynamic_mutations_total"].values()) > 0, sorted(snap)
    assert sum(snap.get("dynamic_repacks_total", {}).values()) >= 1, \
        "governor never re-packed under churn"
    assert any(d.action == "repack" for d in g.decisions), \
        [d.action for d in g.decisions]
    assert any(d.advisory is not None for d in g.decisions), \
        "no drift advisory fired at a 5% nnz threshold"
# exactness after the whole governed stream: dynamic view == fresh pack
m = g.dyn.to_csr()
B = jnp.asarray(rng.standard_normal((m.n_cols, 16)), jnp.float32)
fresh = build_pcsr(m.indptr, m.indices, m.data, m.n_rows, m.n_cols,
                   g.config)
np.testing.assert_allclose(np.asarray(g.spmm(B)),
                           np.asarray(make_spmm_fn(fresh)(B)),
                           rtol=1e-6, atol=1e-6)
print("dynamic smoke: OK (repacks="
      f"{sum(d.action == 'repack' for d in g.decisions)})")
EOF
# serve smoke: a seeded bursty stream through the serving driver with
# per-request full-pipeline verification (--check) — asserts the
# bucketed forward is exact, the steering-pack cache gets hits on a
# replayed workload, and the compiled-bucket count stays below the
# batch count (the zero-recompile acceptance path, see docs/SERVING.md)
SERVE_STATS="$(mktemp /tmp/serve_smoke.XXXXXX.json)"
python -m repro.apps.serve_gnn --graph ba10k --requests 16 --check \
    --stats "$SERVE_STATS"
python - "$SERVE_STATS" <<'EOF'
import json, sys
s = json.load(open(sys.argv[1]))
assert s["checked"] == s["requests"] == 16, s
assert s["cache_hits"] > 0, "no steering-pack cache hits on the stream"
assert s["cache_hits"] + s["cache_misses"] == s["batches"], s
assert 0 < s["compiled_buckets"] <= len(s["buckets"]) < s["batches"], s
EOF
rm -f "$SERVE_STATS"
# perf-trajectory artifact: measured kernel/elementwise-pass counts for
# the fused GNN hot path + fused-vs-unfused pricing, the distributed
# per-shard config table and overlap on/off column, the skewed-corpus
# balanced-vs-uniform schedule smoke (priced + measured makespan), the
# priced-vs-measured rank correlations (small tier, pre/post fit), the
# calibrated-decider agreement/regret table, and the dynamic-graph churn
# columns (degraded-vs-fresh gap, governor trigger points, pre/post-
# repack agreement), plus the serving tier's p50/p99 latency,
# throughput, and steering-pack cache hit rate under seeded replay —
# all in one machine-readable, schema-validated BENCH_spmm.json, with
# the whole sweep traced (run.py records the trace path in the payload)
python -m benchmarks.run \
    --only fusion,dist,spmm,calibration,decider,dynamic,serve \
    --json BENCH_spmm.json --trace BENCH_trace.json
python -m repro.apps.obs_report BENCH_trace.json --top 5
python - <<'EOF'
import json
p = json.load(open("BENCH_spmm.json"))
assert p.get("trace") == "BENCH_trace.json", p.get("trace")
assert "decider" in p and "agreement" in p["decider"], sorted(p)
assert "dynamic" in p and p["dynamic"]["graphs"], sorted(p)
assert "serve" in p and p["serve"]["runs"], sorted(p)
for run in p["serve"]["runs"]:
    assert run["latency_us_p99"] >= run["latency_us_p50"] > 0, run
    assert 0.0 <= run["cache_hit_rate"] <= 1.0, run
for name, gm in p["dynamic"]["graphs"].items():
    # acceptance: after the re-pack the config in use is again the one
    # the model would pick fresh — agreement returns to baseline
    assert gm["agreement_post_repack"] == gm["agreement_fresh"] == 1, \
        (name, gm)
EOF
echo "ci: OK"
