"""GNN inference serving driver — seeded stream replay through
``repro.serve`` (docs/SERVING.md).

    PYTHONPATH=src python -m repro.apps.serve_gnn \\
        --graph rmat13 --model gcn --requests 32 --check

Replays a seeded bursty synthetic request stream through ``GNNService``
and prints per-bucket traffic, cache hit/miss, and latency percentiles.
``--check`` re-runs every request through the full-pipeline reference
forward (same subgraph, same config, no bucketing) and asserts the
served outputs match.  ``--stats PATH`` writes the summary JSON the CI
smoke asserts on; ``--trace PATH`` wraps the run in ``repro.obs``
tracing (serve spans + counters exported as Chrome-trace JSON).
"""
from __future__ import annotations

import argparse
import contextlib
import json

import numpy as np


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--graph", default="rmat13",
                    help="corpus('serve') graph name")
    ap.add_argument("--model", default="gcn",
                    choices=["gcn", "gin", "gat"])
    ap.add_argument("--backend", default=None,
                    choices=["engine", "pallas"],
                    help="default: compiled Pallas kernels on a TPU, the "
                    "pure-JAX engine elsewhere")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--feat", type=int, default=16)
    ap.add_argument("--hidden", type=int, default=32)
    ap.add_argument("--classes", type=int, default=8)
    ap.add_argument("--tick-every", type=int, default=8)
    ap.add_argument("--cache-capacity", type=int, default=8)
    ap.add_argument("--check", action="store_true",
                    help="assert served outputs match the full-pipeline "
                    "reference forward on every request")
    ap.add_argument("--stats", default=None, metavar="PATH",
                    help="write summary JSON")
    ap.add_argument("--trace", nargs="?", const="serve_trace.json",
                    default=None, metavar="PATH")
    args = ap.parse_args(argv)

    import jax
    from repro.compile_cache import enable_compile_cache
    from repro.data.graphs import corpus
    from repro.models.gnn import init_gat, init_gcn, init_gin
    from repro.obs import metrics_snapshot, tracing
    from repro.serve import (GNNService, reference_forward, replay,
                             synthetic_stream)

    specs = {s.name: s for s in corpus("serve")}
    if args.graph not in specs:
        ap.error(f"--graph must be one of {sorted(specs)}")
    g = specs[args.graph].csr
    if args.model != "gat":
        g = g.gcn_normalize()

    rng = np.random.default_rng(args.seed)
    feats = rng.integers(0, 4, (g.n_rows, args.feat)).astype(np.float32)
    key = jax.random.PRNGKey(args.seed)
    dims = [args.feat, args.hidden, args.classes]
    init = {"gcn": init_gcn, "gin": init_gin, "gat": init_gat}[args.model]
    params = init(key, dims)

    stream = synthetic_stream(args.requests, g.n_rows, seed=args.seed)
    enable_compile_cache()
    ctx = tracing(args.trace) if args.trace else contextlib.nullcontext()
    with ctx:
        svc = GNNService(g, feats, params, model=args.model,
                         backend=args.backend,
                         cache_capacity=args.cache_capacity,
                         keep_subgraphs=args.check)
        results = replay(svc, stream, tick_every=args.tick_every)
        snap = {k: v for k, v in metrics_snapshot().items()
                if k.startswith("serve_")}

    assert len(results) == args.requests
    lat = np.array([r.latency_s for r in results]) * 1e3
    cache = svc.cache
    per_bucket: dict = {}
    for r in results:
        per_bucket[r.bucket_key] = per_bucket.get(r.bucket_key, 0) + 1

    checked = 0
    if args.check:
        for r in results:
            sr = r.sampled
            ref = np.asarray(reference_forward(
                sr.sub, feats[sr.nodes], params, model=args.model,
                config=r.config, backend=svc.backend))[sr.seed_local]
            np.testing.assert_allclose(r.outputs, ref, rtol=1e-5,
                                       atol=1e-5,
                                       err_msg=f"request {r.rid}")
            checked += 1
        assert cache.hits > 0, "no steering-pack cache hits on the stream"
        print(f"check: {checked}/{len(results)} requests match the "
              f"full-pipeline reference")

    stats = {
        "graph": args.graph, "model": args.model, "backend": svc.backend,
        "requests": len(results), "batches": len(svc.batch_log),
        "buckets": per_bucket,
        "cache_hits": cache.hits, "cache_misses": cache.misses,
        "cache_evictions": cache.evictions,
        "cache_hit_rate": cache.hit_rate,
        "compiled_buckets": svc.compiled_buckets,
        "latency_ms_p50": float(np.percentile(lat, 50)),
        "latency_ms_p99": float(np.percentile(lat, 99)),
        "checked": checked,
    }
    if args.trace:
        stats["counters"] = snap
    print(f"served {stats['requests']} requests in {stats['batches']} "
          f"batches across {len(per_bucket)} buckets "
          f"({svc.compiled_buckets} compiled)")
    print(f"cache: {cache.hits} hits / {cache.misses} misses "
          f"(hit rate {cache.hit_rate:.2f})")
    print(f"latency p50 {stats['latency_ms_p50']:.1f} ms, "
          f"p99 {stats['latency_ms_p99']:.1f} ms")
    if args.stats:
        with open(args.stats, "w") as fh:
            json.dump(stats, fh, indent=2)
        print(f"# wrote {args.stats}")
    return stats


if __name__ == "__main__":
    main()
