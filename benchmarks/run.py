"""Benchmark harness — one function per paper table/figure.
Prints ``name,us_per_call,derived`` CSV rows (see benchmarks/common.py).

  table1 — blocking (V) vs data locality          [paper Table 1]
  fig1   — workload balancing (S) vs CV           [paper Fig. 1]
  table2 — optimal-F distribution + MAC gap       [paper Table 2]
  table5 — decider accuracy                       [paper Table 5]
  table4 — speedups vs baseline families          [paper Table 4/Fig. 4]
  table6 — reordering ablation                    [paper Table 6]
  fig5   — GCN/GIN end-to-end training            [paper Fig. 5]
  kernel — Pallas-kernel roofline terms           [§Roofline]
  sddmm  — SDDMM + fused GAT message timings      [attention extension]
  dist   — partitioned SpMM/GAT scaling, per-     [distributed extension]
           shard adaptive-config table, halo/
           compute overlap on/off column
  fusion — kernel/elementwise-pass counts +       [fusion extension]
           fused-vs-unfused pricing
  spmm   — balanced-vs-uniform chunk schedule     [B-mode extension]
           priced + measured makespan on the
           skewed corpus
  calibration — priced-vs-measured Spearman ρ     [calibration extension]
           per corpus tier, pre/post NNLS fit of
           the cost-model constants
  decider — decider retrained on calibrated       [observability extension]
           labels: decider-vs-oracle agreement
           + regret on held-out graphs
  dynamic — mutation-stream throughput, de-       [dynamic-graph extension]
           graded-vs-fresh priced + measured
           gap, governor trigger points, pre/
           post-repack decider agreement
  serve  — request-serving p50/p99 latency +      [serving tier]
           throughput under seeded load replay,
           steering-pack cache hit rate

``--json [PATH]`` additionally writes the machine-readable
``BENCH_spmm.json`` (default path): every emitted CSV row plus the
fusion/dist/spmm/calibration/decider/dynamic/serve sections' structured
metrics
(kernel counts, elementwise-pass counts, per-config fused/unfused
times, per-shard configs, overlap on/off timings, fitted coefficients
and rank correlations, decider agreement/regret) — the perf-trajectory
artifact CI archives from PR 4 on (dist folded in from PR 5,
calibration from PR 7, decider from PR 8).  Every row is checked
against the golden schema (``common.validate_row``) before the file is
written.

``--trace [PATH]`` runs the whole sweep under ``repro.obs`` tracing:
one span per benchmark job, the full pack/decision instrumentation
underneath, exported as Chrome-trace JSON; with ``--json`` the trace
path is recorded in the payload next to the rows.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import time


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated benchmark keys")
    ap.add_argument("--json", nargs="?", const="BENCH_spmm.json",
                    default=None, metavar="PATH",
                    help="write BENCH_spmm.json (rows + fusion metrics)")
    ap.add_argument("--trace", nargs="?", const="BENCH_trace.json",
                    default=None, metavar="PATH",
                    help="write a repro.obs Chrome-trace JSON of the "
                    "sweep (read with repro.apps.obs_report / Perfetto)")
    args = ap.parse_args(argv)

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (bench_balancing, bench_blocking,
                            bench_calibration, bench_coarsening,
                            bench_decider, bench_dist, bench_dynamic,
                            bench_fusion, bench_gnn_train, bench_kernel,
                            bench_reorder, bench_sddmm, bench_serve,
                            bench_speedups, bench_spmm)
    from benchmarks.common import ROWS, emit, validate_row

    print("name,us_per_call,derived")
    jobs = {
        "table1": bench_blocking.run,
        "fig1": bench_balancing.run,
        "table2": bench_coarsening.run,
        "table5": bench_decider.run,     # also trains + saves the decider
        "table4": None,                  # needs the trained decider
        "table6": bench_reorder.run,
        "fig5": bench_gnn_train.run,
        "kernel": bench_kernel.run,
        "sddmm": bench_sddmm.run,
        "dist": bench_dist.run,
        "fusion": bench_fusion.run,      # returns structured metrics
        "spmm": bench_spmm.run,          # returns structured metrics
        "calibration": bench_calibration.run,  # returns structured metrics
        "decider": bench_decider.run_calibrated,  # returns structured
        "dynamic": bench_dynamic.run,    # returns structured metrics
        "serve": bench_serve.run,        # returns structured metrics
    }
    only = set(args.only.split(",")) if args.only else set(jobs)
    decider = None
    extras = {}
    from repro.obs import span, tracing
    ctx = tracing(args.trace) if args.trace else contextlib.nullcontext()
    with ctx:
        for key, fn in jobs.items():
            if key not in only:
                continue
            t0 = time.time()
            with span(f"bench.{key}"):
                if key == "table5":
                    decider = fn()
                elif key == "table4":
                    bench_speedups.run(decider)
                elif key in ("fusion", "dist", "spmm", "calibration",
                             "decider", "dynamic", "serve"):  # → JSON
                    extras[key] = fn()
                else:
                    fn()
            emit(f"{key}/__elapsed", (time.time() - t0) * 1e6, "")
    if args.trace:
        print(f"# wrote {args.trace}", flush=True)

    if args.json:
        rows = [{"name": n, "us_per_call": us, "derived": d}
                for n, us, d in ROWS]
        for row in rows:                 # golden schema — fail loud, not
            validate_row(row)            # after the artifact is archived
        payload = {"rows": rows, **extras}
        if args.trace:
            payload["trace"] = args.trace   # the run's telemetry artifact
        with open(args.json, "w") as fh:
            json.dump(payload, fh, indent=2)
        print(f"# wrote {args.json}", flush=True)


if __name__ == "__main__":
    main()
