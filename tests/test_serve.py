"""Serving tier (repro.serve) — exactness, soak/replay, cache semantics.

The serving contract under test (docs/SERVING.md):

* **Exactness** — the bucket-padded serving forward equals the
  full-pipeline forward on the same extracted subgraph: bit-equal for
  GCN/GIN with integer-valued data (padding adds exact zeros; integer
  sums are order-free), float-tolerance for GAT (the softmax normalizer
  is summed in layout order), on BOTH backends.
* **Zero recompiles** — after one warm-up per shape bucket, the jitted
  bucket forward never retraces: asserted via the trace-time
  ``serve_recompiles_total`` counter and (pallas) ``compiles_total``.
* **Determinism** — same seeded stream → same batch composition and
  bit-identical outputs.
* **Cache semantics** — distinct buckets never alias, identical buckets
  hit, hit/miss/eviction counters move exactly as scripted.
"""
import numpy as np
import pytest

import repro.obs as obs
from repro.core.pcsr import SUBLANES, SpMMConfig, build_pcsr, pad_pcsr
from repro.core.sparse import CSRMatrix
from repro.data.graphs import er, extract_subgraph, rmat, sample_khop
from repro.serve import (BucketPolicy, GNNService, PackGeom, RequestBatcher,
                         SampledRequest, ShapeBucket, SteeringPackCache,
                         SubgraphRequest, pack_subgraph, reference_forward,
                         replay, synthetic_stream)

from _propcheck import integers, propcases, sampled_from


@pytest.fixture(autouse=True)
def _reset_obs():
    yield
    if obs.trace_enabled():           # pragma: no cover - safety net
        obs.stop_tracing()
    obs.reset_metrics()


def _int_params(params, scale=3.0):
    """Round params to integer values: integer data makes GCN/GIN sums
    exact under any summation order → bit-equality is well-defined."""
    return [{k: np.round(np.asarray(v) * scale) for k, v in l.items()}
            for l in params]


def _int_feats(rng, n, f):
    return rng.integers(0, 3, (n, f)).astype(np.float32)


def _graph(seed, normalize=False):
    g = rmat(10, 6, seed=seed)
    g.data = np.ones_like(g.data)      # integer weights for exactness
    return g.gcn_normalize() if normalize else g


# ------------------------------------------------------------- sampling
def test_sample_khop_deterministic_and_fanout_bounded():
    g = _graph(1)
    seeds = [3, 77, 500]
    a = sample_khop(g, seeds, (4, 2), seed=9)
    b = sample_khop(g, seeds, (4, 2), seed=9)
    assert np.array_equal(a, b)
    assert np.array_equal(a, np.unique(a)), "sorted unique"
    assert set(seeds) <= set(a.tolist()), "seeds always included"
    # hop-1 cap: at most 4 new nodes per seed
    hop1 = sample_khop(g, seeds, (4,), seed=9)
    assert hop1.size <= len(seeds) + 4 * len(seeds)
    # different sampling seed explores a different neighborhood
    c = sample_khop(g, seeds, (4, 2), seed=10)
    full = sample_khop(g, seeds, (10**6, 10**6), seed=0)
    if full.size > a.size:             # capped sampling has freedom
        assert not np.array_equal(a, c) or a.size == full.size


def test_sample_khop_empty_neighborhood_seed():
    # node n-1 is isolated by construction
    base = er(200, 4, seed=3)
    g = CSRMatrix(np.concatenate([base.indptr, [base.indptr[-1]]]),
                  base.indices, base.data, base.n_rows + 1, base.n_cols + 1)
    iso = g.n_rows - 1
    got = sample_khop(g, [iso], (4, 4), seed=0)
    assert np.array_equal(got, [iso])
    sub = extract_subgraph(g, got)
    assert sub.n_rows == 1 and sub.indices.size == 0


@pytest.mark.parametrize("case", propcases(
    4, seed=integers(0, 100), n=integers(20, 200)), ids=str)
def test_extract_subgraph_matches_dense_oracle(case):
    g = er(case.n + 10, 5, seed=case.seed)
    rng = np.random.default_rng(case.seed)
    nodes = np.unique(rng.integers(0, g.n_rows, case.n))
    sub = extract_subgraph(g, nodes)
    ref = g.to_dense()[np.ix_(nodes, nodes)]
    assert np.array_equal(sub.to_dense(), ref)


# ------------------------------------------------------------ pad_pcsr
@pytest.mark.parametrize("case", propcases(
    6,
    seed=integers(0, 1000),
    config=sampled_from([SpMMConfig(V=1, S=False, W=8),
                         SpMMConfig(V=2, S=True, W=8),
                         SpMMConfig(V=1, S=True, W=16, B=True)]),
    n=integers(10, 180)), ids=str)
def test_pad_pcsr_preserves_matrix_and_invariants(case):
    g = er(case.n, 5, seed=case.seed)
    geom = PackGeom.from_bucket(ShapeBucket(256, 2048), case.config)
    padded = pack_subgraph(g, geom)
    # fixed geometry regardless of input
    assert (padded.n_rows, padded.num_chunks, padded.K) == \
        (geom.n_rows, geom.num_chunks, geom.K)
    # exact same matrix in the live corner
    dense = np.zeros((geom.n_rows, geom.n_rows), np.float32)
    from repro.core.pcsr import pcsr_to_coo
    r, c, v = pcsr_to_coo(padded)
    dense[r, c] = v
    assert np.array_equal(dense[:case.n, :case.n], g.to_dense())
    assert not dense[case.n:].any() and not dense[:, case.n:].any()
    # zero empty blocks → covered steering is the identity
    assert padded.n_empty_blocks == 0
    assert padded.covered_num_chunks == padded.num_chunks
    # grouped trow: each block's chunks contiguous, epilogue fires once
    tr = padded.trow
    firsts = tr[np.concatenate([[0], np.flatnonzero(np.diff(tr)) + 1])]
    assert len(firsts) == len(np.unique(firsts))
    assert padded.fini.sum() == len(np.unique(tr)) == geom.n_blocks


def test_pack_shapes_identical_across_different_subgraphs():
    geom = PackGeom.from_bucket(ShapeBucket(256, 2048),
                                SpMMConfig(V=1, S=True, W=8))
    shapes = []
    for seed in (1, 2):
        p = pack_subgraph(er(100 + 40 * seed, 6, seed=seed), geom)
        st = p.steering()
        shapes.append({k: v.shape for k, v in st.items()})
    assert shapes[0] == shapes[1]


def test_build_pcsr_capacity_override():
    g = er(100, 6, seed=0)
    p = build_pcsr(g.indptr, g.indices, g.data, g.n_rows, g.n_cols,
                   SpMMConfig(V=1, S=True, W=8), capacity=40)
    assert p.K == 40                   # already sublane-aligned
    p2 = build_pcsr(g.indptr, g.indices, g.data, g.n_rows, g.n_cols,
                    SpMMConfig(V=1, S=True, W=8), capacity=3)
    assert p2.K == SUBLANES            # rounded up to the sublane quantum


def test_pad_pcsr_rejects_insufficient_budget():
    g = er(60, 6, seed=0)
    cfg = SpMMConfig(V=1, S=True, W=8)
    p = build_pcsr(g.indptr, g.indices, g.data, g.n_rows, g.n_cols, cfg)
    with pytest.raises(ValueError, match="chunk budget"):
        pad_pcsr(p, n_rows=128, num_chunks=1)
    with pytest.raises(ValueError, match="smaller than"):
        pad_pcsr(p, n_rows=16, num_chunks=1000)


def test_pad_pcsr_empty_graph():
    empty = CSRMatrix(np.zeros(33, np.int64), np.zeros(0, np.int64),
                      np.zeros(0, np.float32), 32, 32)
    geom = PackGeom.from_bucket(ShapeBucket(64, 512),
                                SpMMConfig(V=1, S=True, W=8))
    p = pack_subgraph(empty, geom)
    assert p.num_chunks == geom.num_chunks and p.n_empty_blocks == 0


# ----------------------------------------------------------- exactness
def _serve_and_reference(model, backend, *, graph_seed, stream_seed,
                         feat=8, hidden=16, out=4, requests=3,
                         policy=None, atol=0.0):
    import jax
    from repro.models.gnn import init_gat, init_gcn, init_gin

    g = _graph(graph_seed, normalize=False)   # integer weights (1.0)
    rng = np.random.default_rng(graph_seed)
    feats = _int_feats(rng, g.n_rows, feat)
    init = {"gcn": init_gcn, "gin": init_gin, "gat": init_gat}[model]
    params = _int_params(init(jax.random.PRNGKey(0), [feat, hidden, out]),
                         scale=2.0)
    svc = GNNService(g, feats, params, model=model, backend=backend,
                     policy=policy, keep_subgraphs=True)
    stream = synthetic_stream(requests, g.n_rows, seed=stream_seed)
    results = replay(svc, stream, tick_every=2)
    assert len(results) == requests
    for r in results:
        sr = r.sampled
        ref = np.asarray(reference_forward(
            sr.sub, feats[sr.nodes], params, model=model,
            config=r.config, backend=backend))[sr.seed_local]
        if atol == 0.0:
            assert np.array_equal(r.outputs, ref), \
                f"{model}/{backend} request {r.rid} not bit-equal"
        else:
            np.testing.assert_allclose(r.outputs, ref, rtol=0, atol=atol,
                                       err_msg=f"{model}/{backend}/{r.rid}")
    return svc, results


@pytest.mark.parametrize("case", propcases(
    4, _seed=3, graph_seed=integers(0, 50), stream_seed=integers(0, 50),
    model=sampled_from(["gcn", "gin", "gat"])), ids=str)
def test_serve_exactness_engine_property(case):
    atol = 1e-5 if case.model == "gat" else 0.0
    _serve_and_reference(case.model, "engine", graph_seed=case.graph_seed,
                         stream_seed=case.stream_seed, atol=atol)


@pytest.mark.parametrize("model", ["gcn", "gat"])
def test_serve_exactness_pallas(model):
    pol = BucketPolicy([ShapeBucket(256, 2048)])
    atol = 1e-5 if model == "gat" else 0.0
    _serve_and_reference(model, "pallas", graph_seed=5, stream_seed=7,
                         requests=2, policy=pol, atol=atol)


def test_serve_exactness_empty_neighborhood_seed():
    import jax
    from repro.models.gnn import init_gcn

    base = _graph(2)
    g = CSRMatrix(np.concatenate([base.indptr, [base.indptr[-1]]]),
                  base.indices, base.data, base.n_rows + 1, base.n_cols + 1)
    iso = g.n_rows - 1
    feats = _int_feats(np.random.default_rng(0), g.n_rows, 8)
    params = _int_params(init_gcn(jax.random.PRNGKey(0), [8, 16, 4]))
    svc = GNNService(g, feats, params, model="gcn", keep_subgraphs=True)
    res = replay(svc, [SubgraphRequest("iso", (iso,), (4, 2), 1),
                       SubgraphRequest("mix", (iso, 3), (4,), 2)],
                 tick_every=1)
    for r in res:
        sr = r.sampled
        ref = np.asarray(reference_forward(
            sr.sub, feats[sr.nodes], params, model="gcn",
            config=r.config))[sr.seed_local]
        assert np.array_equal(r.outputs, ref)
    # the isolated seed aggregates nothing: output = bias path only
    assert res[0].outputs.shape == (1, 4)


def test_serve_exactness_bucket_ceiling_exact_size():
    """A batch landing EXACTLY on the node ceiling still packs (the +R
    headroom block hosts the filler chunks) and stays exact."""
    import jax
    from repro.models.gnn import init_gcn

    g = _graph(4)
    # find a request whose subgraph is then padded to exactly n_ceil
    nodes = sample_khop(g, [1, 2, 3], (8, 8), seed=1)
    pol = BucketPolicy([ShapeBucket(int(nodes.size), 4096)])
    feats = _int_feats(np.random.default_rng(1), g.n_rows, 8)
    params = _int_params(init_gcn(jax.random.PRNGKey(1), [8, 16, 4]))
    svc = GNNService(g, feats, params, model="gcn", policy=pol,
                     keep_subgraphs=True)
    res = replay(svc, [SubgraphRequest("edge", (1, 2, 3), (8, 8), 1)],
                 tick_every=1)
    sr = res[0].sampled
    assert sr.n == pol.largest.n_ceil            # ceiling-exact
    ref = np.asarray(reference_forward(
        sr.sub, feats[sr.nodes], params, model="gcn",
        config=res[0].config))[sr.seed_local]
    assert np.array_equal(res[0].outputs, ref)


# ---------------------------------------------------------- soak/replay
def _recompile_total(snap):
    return sum(snap.get("serve_recompiles_total", {}).values())


def test_soak_replay_deterministic_and_zero_recompiles():
    """Seeded bursty stream, twice: identical batch composition, bit-
    identical outputs, and — via the trace-time recompile counter — one
    compilation per bucket on warm-up, ZERO for the rest of the run."""
    import jax
    from repro.models.gnn import init_gcn

    g = _graph(6)
    feats = _int_feats(np.random.default_rng(2), g.n_rows, 24)
    # distinctive dims → this test owns its jit cache entries
    params = _int_params(init_gcn(jax.random.PRNGKey(2), [24, 40, 6]))
    pol = BucketPolicy([ShapeBucket(256, 2048), ShapeBucket(512, 4096),
                        ShapeBucket(1024, 8192)])
    stream = synthetic_stream(24, g.n_rows, seed=13)

    with obs.tracing():
        svc1 = GNNService(g, feats, params, model="gcn", policy=pol)
        out1 = replay(svc1, stream, tick_every=4)
        warm = _recompile_total(obs.metrics_snapshot())
        buckets_used = {b for b, _ in svc1.batch_log}
        assert warm == svc1.compiled_buckets == len(buckets_used) > 0
        # the REST of the stream (after each bucket's first batch) plus a
        # full second pass recompiled nothing
        svc2 = GNNService(g, feats, params, model="gcn", policy=pol)
        out2 = replay(svc2, stream, tick_every=4)
        assert _recompile_total(obs.metrics_snapshot()) == warm, \
            "recompilation after warm-up"

    assert svc1.batch_log == svc2.batch_log, "batch composition drifted"
    for a, b in zip(out1, out2):
        assert a.rid == b.rid and a.bucket_key == b.bucket_key
        assert np.array_equal(a.outputs, b.outputs)


def test_soak_pallas_calls_flat_after_warmup():
    """Pallas backend: ``compiles_total`` moves only when JAX traces or
    compiles, so a flat counter across a replayed stream proves the
    kernels compiled once per bucket."""
    import jax
    from repro.models.gnn import init_gcn

    g = rmat(9, 5, seed=8)
    g.data = np.ones_like(g.data)
    feats = _int_feats(np.random.default_rng(3), g.n_rows, 8)
    params = _int_params(init_gcn(jax.random.PRNGKey(3), [8, 16, 4]))
    pol = BucketPolicy([ShapeBucket(128, 1024)])
    stream = synthetic_stream(4, g.n_rows, seed=17)

    def compiles_total():
        snap = obs.metrics_snapshot()
        return sum(snap.get("compiles_total", {}).values())

    with obs.tracing():
        svc = GNNService(g, feats, params, model="gcn", backend="pallas",
                         policy=pol)
        replay(svc, stream, tick_every=2)
        warm = compiles_total()
        assert warm > 0, "warm-up compiled nothing"
        svc2 = GNNService(g, feats, params, model="gcn", backend="pallas",
                          policy=pol)
        replay(svc2, stream, tick_every=2)
        assert compiles_total() == warm, "pallas kernels re-traced"


# ------------------------------------------------------ cache semantics
def test_cache_scripted_hits_misses_and_no_aliasing():
    a, b = ShapeBucket(128, 512), ShapeBucket(256, 1024)
    g = er(100, 5, seed=0)
    with obs.tracing():
        cache = SteeringPackCache(dim=16, capacity=4)
        pa1 = cache.get(a, g)
        pa2 = cache.get(a, g)
        pb = cache.get(b, g)
        snap = obs.metrics_snapshot()
    assert (cache.hits, cache.misses, cache.evictions) == (1, 2, 0)
    assert pa1 is pa2, "identical buckets must hit"
    assert pa1.geom != pb.geom, "distinct buckets must never alias"
    assert snap["serve_cache_hits_total"] == {f"bucket={a.key}": 1.0}
    assert snap["serve_cache_misses_total"] == {f"bucket={a.key}": 1.0,
                                                f"bucket={b.key}": 1.0}
    assert cache.hit_rate == pytest.approx(1 / 3)


def test_cache_capacity_bounded_eviction():
    a, b = ShapeBucket(128, 512), ShapeBucket(256, 1024)
    g = er(80, 5, seed=1)
    with obs.tracing():
        cache = SteeringPackCache(dim=16, capacity=1)
        cache.get(a, g)
        cache.get(b, g)                # evicts a
        cache.get(a, g)                # miss again, evicts b
        snap = obs.metrics_snapshot()
    assert (cache.hits, cache.misses, cache.evictions) == (0, 3, 2)
    assert len(cache) == 1
    assert sum(snap["serve_cache_evictions_total"].values()) == 2


# ------------------------------------------------------------- batching
def _fake_sampled(rid, n, e):
    rows = np.zeros(e, np.int64)
    cols = np.arange(e) % max(n, 1)
    sub = CSRMatrix.from_coo(rows, cols, np.ones(e, np.float32), n, n,
                             sum_duplicates=False)
    return SampledRequest(SubgraphRequest(rid, (0,), (1,)),
                          np.arange(n), sub, np.zeros(1, np.int64))


def test_batcher_greedy_fifo_composition():
    bat = RequestBatcher(n_max=100, e_max=1000, max_batch=3)
    for i, n in enumerate([40, 40, 40, 10, 10, 10, 10, 90]):
        bat.add(_fake_sampled(f"r{i}", n, 5))
    groups = [[sr.req.rid for sr in b] for b in bat.drain()]
    assert groups == [["r0", "r1"], ["r2", "r3", "r4"],
                      ["r5", "r6"], ["r7"]]
    assert len(bat) == 0


def test_batcher_rejects_oversize_request():
    bat = RequestBatcher(n_max=50, e_max=100)
    with pytest.raises(ValueError, match="exceeds the largest bucket"):
        bat.add(_fake_sampled("big", 60, 5))


def test_synthetic_stream_deterministic():
    s1 = synthetic_stream(10, 1000, seed=4)
    s2 = synthetic_stream(10, 1000, seed=4)
    assert s1 == s2
    assert [r.rid for r in s1] == [f"r{i}" for i in range(10)]
    assert all(s1[i].arrival_s <= s1[i + 1].arrival_s
               for i in range(len(s1) - 1))
    assert synthetic_stream(10, 1000, seed=5) != s1
