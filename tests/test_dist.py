"""Distributed graph subsystem (repro.dist).

Two tiers in one module:

* host-side partition/halo property tests — run on any device count;
* mesh execution tests (dist_spmm / dist_gat_message vs the
  single-device engine, fwd + grads) — need ≥ 2 devices, which CPU hosts
  only have under ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
  (scripts/ci.sh runs this module that way in its own process).
"""
import numpy as np
import pytest

import _propcheck as pc
from conftest import random_csr

import jax
import jax.numpy as jnp

from repro.core import (CostModel, CSRMatrix, SpMMConfig, build_pcsr,
                        config_space, extract_features, transpose_pcsr)
from repro.core.engine import engine_spmm, make_gat_message_fn, make_spmm_fn
from repro.data.graphs import er, grid2d, rmat, sbm
from repro.dist import (DistGraph, build_halo, dist_gat_message, dist_spmm,
                        partition_bounds, partition_csr, split_local_halo,
                        unpartition_rows)

needs_mesh = pytest.mark.skipif(
    jax.device_count() < 2,
    reason="needs >1 device (XLA_FLAGS=--xla_force_host_platform_"
           "device_count=8)")


def _graph(kind, seed):
    if kind == "rmat":
        return rmat(9, 8, seed=seed)
    if kind == "er":
        return er(700, 6, seed=seed)
    if kind == "grid":
        return grid2d(26, seed=seed)
    return sbm(6, 96, 0.2, 1.0, seed=seed)


def _global_coo(csr):
    rows = np.repeat(np.arange(csr.n_rows), csr.degrees)
    return set(zip(rows.tolist(), csr.indices.tolist(),
                   np.round(csr.data, 5).tolist()))


# ------------------------------------------------- partition invariants
@pytest.mark.parametrize("case", pc.propcases(
    12, kind=pc.sampled_from(["rmat", "er", "grid", "sbm"]),
    n_parts=pc.integers(1, 7),
    strategy=pc.sampled_from(["contiguous", "balanced"]),
    seed=pc.integers(0, 10**6)), ids=str)
def test_partition_covers_every_nnz_exactly_once(case):
    csr = _graph(case.kind, case.seed)
    part = partition_csr(csr, case.n_parts, case.strategy)
    # shard nnz counts sum to the global nnz
    assert sum(s.csr.nnz for s in part.shards) == csr.nnz
    # and the union of shard edge sets, mapped back to global ids,
    # reproduces the original edge set exactly (values included)
    rebuilt = set()
    for s in part.shards:
        rows = np.repeat(np.arange(s.csr.n_rows), s.csr.degrees) + s.start
        cols = s.csr.indices.copy()
        local = cols < part.rows_pad
        assert np.all(rows < s.stop), "edge scattered outside its shard"
        cols = np.where(local, cols + s.start,
                        -1 if s.n_halo == 0 else
                        s.halo_global[np.clip(cols - part.rows_pad, 0,
                                              max(0, s.n_halo - 1))])
        # halo references must stay inside the true halo range
        assert np.all(s.csr.indices[~local] - part.rows_pad < s.n_halo)
        rebuilt |= set(zip(rows.tolist(), cols.tolist(),
                           np.round(s.csr.data, 5).tolist()))
    assert rebuilt == _global_coo(csr)


@pytest.mark.parametrize("case", pc.propcases(
    8, kind=pc.sampled_from(["rmat", "er", "sbm"]),
    n_parts=pc.integers(2, 6),
    seed=pc.integers(0, 10**6)), ids=str)
def test_halo_maps_are_consistent(case):
    csr = _graph(case.kind, case.seed)
    part = partition_csr(csr, case.n_parts, "balanced")
    halo = build_halo(part)
    for p, s in enumerate(part.shards):
        assert halo.n_halo[p] == s.n_halo
        # halo columns are foreign, sorted, unique
        own = part.owner(s.halo_global)
        assert np.all(own != p)
        assert np.all(np.diff(s.halo_global) > 0)
        # each halo entry's flat gathered position points at a send slot
        # of the owner that holds exactly that global row
        for h in range(s.n_halo):
            flat = int(halo.halo_src[p, h])
            q, slot = divmod(flat, halo.max_send)
            assert q == own[h] and slot < halo.n_send[q]
            g = int(halo.send_idx[q, slot]) + int(part.starts[q])
            assert g == int(s.halo_global[h])


def test_balanced_strategy_bounds_shard_nnz():
    csr = rmat(11, 8, seed=3)          # power-law: contiguous is skewed
    part = partition_csr(csr, 4, "balanced")
    target = csr.nnz / 4
    slack = int(csr.degrees.max())
    for s in part.shards:
        assert s.csr.nnz <= target + slack


def test_pad_position_roundtrip():
    csr = er(311, 5, seed=9)           # odd n: shards pad unevenly
    part = partition_csr(csr, 3, "contiguous")
    x = np.arange(csr.n_rows)
    stacked = np.zeros(part.n_parts * part.rows_pad, np.int64)
    stacked[part.pad_position(x)] = x
    assert np.array_equal(unpartition_rows(part, stacked), x)


def test_partition_rejects_bad_inputs():
    csr = er(64, 4, seed=0)
    with pytest.raises(ValueError):
        partition_bounds(csr, 0)
    with pytest.raises(ValueError):
        partition_bounds(csr, 4, "zigzag")
    rect = CSRMatrix(np.array([0, 1]), np.array([2]),
                     np.ones(1, np.float32), 1, 8)
    with pytest.raises(ValueError):
        partition_csr(rect, 2)


def test_distgraph_plan_is_device_free():
    """Constructing a DistGraph is a host-side plan: partitioning and
    per-shard config selection must work for more partitions than the
    host has devices (the mesh is only resolved on first call)."""
    csr = rmat(8, 6, seed=4)
    n_parts = jax.device_count() + 3
    g = DistGraph(csr, 16, n_parts, strategy="balanced")
    assert len(g.configs) == n_parts
    assert len(g.predicted_times) == n_parts
    with pytest.raises(ValueError, match="devices"):
        _ = g.mesh


def test_core_package_exports():
    # the satellite: downstream code imports repro.core, not submodules
    assert SpMMConfig(V=2, W=4).R == 8
    assert callable(build_pcsr) and callable(transpose_pcsr)
    assert callable(extract_features) and callable(CostModel)
    assert len(config_space(64)) > 0


# ------------------------------------------------------ mesh execution
def _dist_tol(a, b):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=2e-4, atol=2e-5)


@needs_mesh
@pytest.mark.parametrize("case", pc.propcases(
    6, kind=pc.sampled_from(["rmat", "er", "grid", "sbm"]),
    n_parts=pc.sampled_from([2, 4]),
    strategy=pc.sampled_from(["contiguous", "balanced"]),
    seed=pc.integers(0, 10**6)), ids=str)
def test_dist_spmm_matches_engine(case):
    csr = _graph(case.kind, case.seed)
    dim = 32
    rng = np.random.default_rng(case.seed)
    B = jnp.asarray(rng.standard_normal((csr.n_rows, dim)), jnp.float32)
    cfg, _ = CostModel(csr).best(dim, config_space(dim))
    ref = engine_spmm(build_pcsr(csr.indptr, csr.indices, csr.data,
                                 csr.n_rows, csr.n_cols, cfg), B)
    g = DistGraph(csr, dim, case.n_parts, strategy=case.strategy)
    _dist_tol(dist_spmm(g, B), ref)


@needs_mesh
def test_dist_spmm_grad_matches_transpose_path():
    csr = rmat(9, 8, seed=5)
    dim = 24
    rng = np.random.default_rng(1)
    B = jnp.asarray(rng.standard_normal((csr.n_rows, dim)), jnp.float32)
    cfg, _ = CostModel(csr).best(dim, config_space(dim))
    p = build_pcsr(csr.indptr, csr.indices, csr.data,
                   csr.n_rows, csr.n_cols, cfg)
    t = csr.transpose()
    pt = build_pcsr(t.indptr, t.indices, t.data, t.n_rows, t.n_cols, cfg)
    ref_fn = make_spmm_fn(p, pt)
    g = DistGraph(csr, dim, 4, strategy="balanced")
    gd = jax.grad(lambda b: (dist_spmm(g, b) ** 2).sum())(B)
    gr = jax.grad(lambda b: (ref_fn(b) ** 2).sum())(B)
    np.testing.assert_allclose(np.asarray(gd), np.asarray(gr),
                               rtol=2e-3, atol=2e-4)


@needs_mesh
def test_dist_gat_message_matches_engine_fwd_and_grads():
    csr = sbm(5, 64, 0.25, 1.0, seed=7)
    rng = np.random.default_rng(2)
    n = csr.n_rows
    Q = jnp.asarray(rng.standard_normal((n, 16)), jnp.float32)
    K = jnp.asarray(rng.standard_normal((n, 16)), jnp.float32)
    Vf = jnp.asarray(rng.standard_normal((n, 20)), jnp.float32)
    cfg, _ = CostModel(csr).best(16, config_space(16), op="gat")
    p = build_pcsr(csr.indptr, csr.indices, csr.data, n, n, cfg)
    ref_fn = make_gat_message_fn(p)
    g = DistGraph(csr, 16, 3, strategy="contiguous", op="gat")
    _dist_tol(dist_gat_message(g, Q, K, Vf), ref_fn(Q, K, Vf))
    loss_d = lambda q, k, v: (dist_gat_message(g, q, k, v) ** 2).sum()
    loss_r = lambda q, k, v: (ref_fn(q, k, v) ** 2).sum()
    gd = jax.grad(loss_d, argnums=(0, 1, 2))(Q, K, Vf)
    gr = jax.grad(loss_r, argnums=(0, 1, 2))(Q, K, Vf)
    for a, b in zip(gd, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-4)


@needs_mesh
def test_dist_spmm_pallas_backend():
    csr = rmat(7, 6, seed=2)           # tiny: interpret-mode kernels
    dim = 16
    rng = np.random.default_rng(0)
    B = jnp.asarray(rng.standard_normal((csr.n_rows, dim)), jnp.float32)
    cfg, _ = CostModel(csr).best(dim, config_space(dim))
    ref = engine_spmm(build_pcsr(csr.indptr, csr.indices, csr.data,
                                 csr.n_rows, csr.n_cols, cfg), B)
    g = DistGraph(csr, dim, 2, backend="pallas", interpret=True)
    _dist_tol(dist_spmm(g, B), ref)
    gd = jax.grad(lambda b: (dist_spmm(g, b) ** 2).sum())(B)
    ge = jax.grad(lambda b: (engine_spmm(
        build_pcsr(csr.indptr, csr.indices, csr.data,
                   csr.n_rows, csr.n_cols, cfg), b) ** 2).sum())(B)
    np.testing.assert_allclose(np.asarray(gd), np.asarray(ge),
                               rtol=2e-3, atol=2e-4)


@needs_mesh
def test_per_partition_configs_adapt_on_powerlaw():
    """The cross-shard adaptivity claim: a power-law graph's balanced
    shards have different density/CV, so the cost model picks different
    ⟨W,F,V,S⟩ per shard — and the result still matches single-device."""
    csr = rmat(10, 8, seed=1)
    dim = 32
    g = DistGraph(csr, dim, 4, strategy="balanced")
    assert len(set(g.configs)) > 1, [c.astuple() for c in g.configs]
    rng = np.random.default_rng(0)
    B = jnp.asarray(rng.standard_normal((csr.n_rows, dim)), jnp.float32)
    cfg, _ = CostModel(csr).best(dim, config_space(dim))
    ref = engine_spmm(build_pcsr(csr.indptr, csr.indices, csr.data,
                                 csr.n_rows, csr.n_cols, cfg), B)
    _dist_tol(dist_spmm(g, B), ref)


@needs_mesh
def test_dist_handles_random_matrices_and_explicit_configs(rng):
    csr, dense = random_csr(rng, 150, density=0.08, skew=True)
    B = jnp.asarray(rng.standard_normal((150, 16)), jnp.float32)
    g = DistGraph(csr, 16, 2, configs=SpMMConfig(V=1, W=8, F=1, S=True))
    assert all(c == SpMMConfig(V=1, W=8, F=1, S=True) for c in g.configs)
    np.testing.assert_allclose(np.asarray(dist_spmm(g, B)),
                               dense @ np.asarray(B),
                               rtol=2e-3, atol=2e-3)


@needs_mesh
@pytest.mark.parametrize("backend", ["engine", "pallas"])
def test_dist_fused_epilogue_matches_dense(backend, rng):
    """DistGraph.fused = act(scale ⊙ (A·B) + bias) with the epilogue
    applied per shard inside the SPMD program — forward vs dense and
    grads (B, bias) vs the single-device fused operator."""
    from repro.core.engine import ParamSpMMOperator

    csr, dense = random_csr(rng, 96, density=0.1, skew=True)
    dim = 12
    B = jnp.asarray(rng.standard_normal((96, dim)), jnp.float32)
    sc = jnp.asarray(rng.random(96) + 0.5, jnp.float32)
    b = jnp.asarray(rng.standard_normal(dim), jnp.float32)
    g = DistGraph(csr, dim, 2, backend=backend, interpret=True)
    out = np.asarray(g.fused(B, scale=sc, bias=b, activation="relu"))
    ref = np.maximum(np.asarray(sc)[:, None] * (dense @ np.asarray(B))
                     + np.asarray(b), 0.0)
    np.testing.assert_allclose(out, ref, rtol=2e-3, atol=2e-3)

    def loss(fused):
        return lambda B, b: (fused(B, scale=sc, bias=b,
                                   activation="relu") ** 2).sum()

    gd = jax.grad(loss(g.fused), (0, 1))(B, b)
    cfg, _ = CostModel(csr).best(dim, config_space(dim))
    op = ParamSpMMOperator(csr, cfg, backend="engine")
    ge = jax.grad(loss(op.fused), (0, 1))(B, b)
    for a, c in zip(gd, ge):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   rtol=2e-3, atol=2e-3)


@needs_mesh
def test_dist_train_gnn_partitions():
    from repro.apps.gnn import train_gnn
    from repro.data.tasks import community_task

    task = community_task(n_blocks=4, block_size=48, seed=0)
    res = train_gnn(task, model="gcn", hidden=32, n_layers=2, steps=8,
                    partitions=2)
    assert isinstance(res.config, list) and len(res.config) == 2
    assert res.losses[-1] < res.losses[0]


# ------------------------------------------- multi-head distributed GAT
def _mh_operands(rng, n, H, da, dv):
    Q = jnp.asarray(rng.standard_normal((H, n, da)), jnp.float32)
    K = jnp.asarray(rng.standard_normal((H, n, da)), jnp.float32)
    Vf = jnp.asarray(rng.standard_normal((H, n, dv)), jnp.float32)
    return Q, K, Vf


def _gat_ref(csr, H, dim):
    cfg, _ = CostModel(csr).best(dim, config_space(dim), op="gat", H=H)
    p = build_pcsr(csr.indptr, csr.indices, csr.data,
                   csr.n_rows, csr.n_rows, cfg)
    return make_gat_message_fn(p)


@needs_mesh
@pytest.mark.parametrize("backend", ["engine", "pallas"])
def test_dist_gat_multihead_matches_engine(backend):
    """Multi-head distributed GAT — fwd and grads vs the single-device
    engine, on both backends (the Pallas backend runs the two-kernel
    fused forward + all-Pallas backward per shard)."""
    csr = sbm(5, 64, 0.25, 1.0, seed=7)
    rng = np.random.default_rng(2)
    H = 2
    Q, K, Vf = _mh_operands(rng, csr.n_rows, H, 16, 20)
    ref_fn = _gat_ref(csr, H, 16)
    g = DistGraph(csr, 16, 3, strategy="balanced", op="gat", heads=H,
                  backend=backend, interpret=True)
    _dist_tol(dist_gat_message(g, Q, K, Vf), ref_fn(Q, K, Vf))
    loss_d = lambda q, k, v: (dist_gat_message(g, q, k, v) ** 2).sum()
    loss_r = lambda q, k, v: (ref_fn(q, k, v) ** 2).sum()
    gd = jax.grad(loss_d, argnums=(0, 1, 2))(Q, K, Vf)
    gr = jax.grad(loss_r, argnums=(0, 1, 2))(Q, K, Vf)
    for a, b in zip(gd, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-4)


@needs_mesh
def test_dist_gat_multihead_empty_shard_pallas():
    """A fully-empty shard (zero local nnz → degenerate PCSR) must ride
    the same head-tiled two-kernel program as its loaded neighbours."""
    rng = np.random.default_rng(4)
    n, P = 96, 4
    A = ((rng.random((n, n)) < 0.12)
         * rng.standard_normal((n, n))).astype(np.float32)
    A[24:48] = 0.0                     # shard 1 of a 4-way contiguous
    csr = CSRMatrix.from_dense(A)      # split owns no edges at all
    H = 3
    Q, K, Vf = _mh_operands(rng, n, H, 8, 12)
    ref_fn = _gat_ref(csr, H, 8)
    g = DistGraph(csr, 8, P, strategy="contiguous", op="gat", heads=H,
                  backend="pallas", interpret=True)
    assert any(s.csr.nnz == 0 for s in g.part.shards)
    _dist_tol(dist_gat_message(g, Q, K, Vf), ref_fn(Q, K, Vf))
    gd = jax.grad(lambda q, k, v:
                  (dist_gat_message(g, q, k, v) ** 2).sum(),
                  argnums=(0, 1, 2))(Q, K, Vf)
    gr = jax.grad(lambda q, k, v: (ref_fn(q, k, v) ** 2).sum(),
                  argnums=(0, 1, 2))(Q, K, Vf)
    for a, b in zip(gd, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-4)


@needs_mesh
def test_dist_gat_multihead_halo_heavy_pallas():
    """Halo-heavy partitions (ER graph: most sources are remote) — the
    joint K/Vf exchange and the dK/dVf scatter-back carry most of the
    gradient mass."""
    csr = er(120, 12, seed=3)
    rng = np.random.default_rng(5)
    H = 2
    Q, K, Vf = _mh_operands(rng, csr.n_rows, H, 8, 8)
    ref_fn = _gat_ref(csr, H, 8)
    g = DistGraph(csr, 8, 3, strategy="balanced", op="gat", heads=H,
                  backend="pallas", interpret=True)
    assert max(s.n_halo for s in g.part.shards) > 40   # genuinely heavy
    _dist_tol(dist_gat_message(g, Q, K, Vf), ref_fn(Q, K, Vf))
    gd = jax.grad(lambda q, k, v:
                  (dist_gat_message(g, q, k, v) ** 2).sum(),
                  argnums=(0, 1, 2))(Q, K, Vf)
    gr = jax.grad(lambda q, k, v: (ref_fn(q, k, v) ** 2).sum(),
                  argnums=(0, 1, 2))(Q, K, Vf)
    for a, b in zip(gd, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-4)


@needs_mesh
def test_dist_gat_pallas_forward_is_two_kernels_per_shard(monkeypatch):
    """The acceptance bar: the distributed multi-head GAT forward
    launches exactly TWO Pallas kernels per shard — the fused
    SDDMM→softmax-stats kernel and the prologue SpMM — with no
    interstitial elementwise pass (α never materializes)."""
    import pathlib
    import sys
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
    from benchmarks.common import count_pallas_calls

    csr = sbm(5, 64, 0.25, 1.0, seed=7)
    rng = np.random.default_rng(2)
    H, P = 2, 3
    Q, K, Vf = _mh_operands(rng, csr.n_rows, H, 16, 20)
    g = DistGraph(csr, 16, P, strategy="contiguous", op="gat", heads=H,
                  backend="pallas", interpret=True)
    calls = count_pallas_calls(lambda: dist_gat_message(g, Q, K, Vf))
    assert len(calls) == 2 * P, calls
    assert sum("sddmm_softmax" in c for c in calls) == P
    assert sum("_pro" in c for c in calls) == P   # prologue-fused SpMM


@needs_mesh
def test_dist_gat_pallas_backward_no_engine_fallback(monkeypatch):
    """The distributed GAT backward is dedicated all-Pallas: grads must
    come out with every engine path stubbed to raise."""
    import repro.core.engine as emod

    csr = sbm(5, 64, 0.25, 1.0, seed=7)
    rng = np.random.default_rng(2)
    H = 2
    Q, K, Vf = _mh_operands(rng, csr.n_rows, H, 16, 20)
    ref_fn = _gat_ref(csr, H, 16)
    gr = jax.grad(lambda q, k, v: (ref_fn(q, k, v) ** 2).sum(),
                  argnums=(0, 1, 2))(Q, K, Vf)

    def _boom(*a, **kw):
        raise AssertionError("engine fallback in the dist Pallas GAT path")

    # the GAT pipeline the shards run lives in core.engine
    monkeypatch.setattr(emod, "_engine", _boom)
    monkeypatch.setattr(emod, "_engine_sddmm", _boom)
    monkeypatch.setattr(emod, "attend_scores", _boom)
    monkeypatch.setattr(emod, "edge_softmax", _boom)
    g = DistGraph(csr, 16, 3, strategy="balanced", op="gat", heads=H,
                  backend="pallas", interpret=True)
    gd = jax.grad(lambda q, k, v:
                  (dist_gat_message(g, q, k, v) ** 2).sum(),
                  argnums=(0, 1, 2))(Q, K, Vf)
    for a, b in zip(gd, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-4)


def test_distgraph_heads_aware_per_shard_configs():
    """Device-free plan check: the per-shard configs are priced for the
    head count — head tiling shrinks the per-head lane width, so the
    shard optima at H=8 differ from H=1 (the dist analogue of the
    head-aware cost-model regression in test_fusion)."""
    rng = np.random.default_rng(0)
    n = 1500
    A = rng.random((n, n)) < 0.004
    rows, cols = np.nonzero(A)
    csr = CSRMatrix.from_coo(rows, cols, np.ones(len(rows), np.float32),
                             n, n)
    g1 = DistGraph(csr, 512, 2, strategy="balanced", op="gat", heads=1)
    g8 = DistGraph(csr, 512, 2, strategy="balanced", op="gat", heads=8)
    assert [c.astuple() for c in g1.configs] \
        != [c.astuple() for c in g8.configs]


# --------------------------------------------------- halo/compute overlap
def test_split_local_halo_partitions_every_edge():
    csr = rmat(9, 8, seed=11)
    part = partition_csr(csr, 4, "balanced")
    for s in part.shards:
        loc, hal = split_local_halo(s, part)
        assert loc.nnz + hal.nnz == s.csr.nnz
        assert loc.n_cols == part.rows_pad and hal.n_cols == part.halo_pad
        if hal.nnz:
            assert hal.indices.max() < s.n_halo


@needs_mesh
@pytest.mark.parametrize("case", pc.propcases(
    4, kind=pc.sampled_from(["rmat", "er", "grid", "sbm"]),
    n_parts=pc.sampled_from([2, 4]),
    backend=pc.sampled_from(["engine", "pallas"]),
    seed=pc.integers(0, 10**6)), ids=str)
def test_dist_spmm_overlap_matches_nonoverlap(case):
    """The overlap decomposition (local + halo sub-SpMMs, gather hidden
    behind the local one) is a pure schedule change: forward and
    backward must match the serialized path numerically."""
    csr = _graph(case.kind, case.seed)
    dim = 16
    rng = np.random.default_rng(case.seed)
    B = jnp.asarray(rng.standard_normal((csr.n_rows, dim)), jnp.float32)
    g0 = DistGraph(csr, dim, case.n_parts, strategy="balanced",
                   backend=case.backend, interpret=True)
    g1 = DistGraph(csr, dim, case.n_parts, strategy="balanced",
                   backend=case.backend, interpret=True, overlap=True)
    _dist_tol(dist_spmm(g1, B), dist_spmm(g0, B))
    gd0 = jax.grad(lambda b: (dist_spmm(g0, b) ** 2).sum())(B)
    gd1 = jax.grad(lambda b: (dist_spmm(g1, b) ** 2).sum())(B)
    np.testing.assert_allclose(np.asarray(gd1), np.asarray(gd0),
                               rtol=2e-3, atol=2e-4)


@needs_mesh
def test_dist_fused_overlap_matches_nonoverlap(rng):
    """Fused epilogue under overlap: applied per shard after the
    local+halo add — same numbers as the in-branch epilogue path."""
    csr = rmat(9, 8, seed=5)
    dim = 12
    n = csr.n_rows
    B = jnp.asarray(rng.standard_normal((n, dim)), jnp.float32)
    sc = jnp.asarray(rng.random(n) + 0.5, jnp.float32)
    b = jnp.asarray(rng.standard_normal(dim), jnp.float32)
    g0 = DistGraph(csr, dim, 4, strategy="balanced")
    g1 = DistGraph(csr, dim, 4, strategy="balanced", overlap=True)
    _dist_tol(g1.fused(B, scale=sc, bias=b, activation="relu"),
              g0.fused(B, scale=sc, bias=b, activation="relu"))

    def loss(g):
        return lambda B, b: (g.fused(B, scale=sc, bias=b,
                                     activation="relu") ** 2).sum()

    gd0 = jax.grad(loss(g0), (0, 1))(B, b)
    gd1 = jax.grad(loss(g1), (0, 1))(B, b)
    for a, c in zip(gd1, gd0):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   rtol=2e-3, atol=2e-4)


@needs_mesh
def test_dist_overlap_adapts_subconfigs():
    """Overlap mode selects configs per *sub-matrix*: on a power-law
    graph the halo part is sparser/more scattered than the local part,
    so at least one shard picks different ⟨W,F,V,S⟩ for the two."""
    csr = rmat(10, 8, seed=1)
    g = DistGraph(csr, 32, 4, strategy="balanced", overlap=True)
    assert len(g.overlap_configs) == 4
    assert any(lc != hc for lc, hc in g.overlap_configs), \
        [(lc.astuple(), hc.astuple()) for lc, hc in g.overlap_configs]


# --------------------------------------------- fused backward dbias fold
@needs_mesh
def test_dist_fused_dbias_reduced_inside_spmd(rng):
    """The PR-4 leftover, fixed: dbias comes out of the SAME shard_map
    program as dB (an in-program psum), not a global reduce outside the
    SPMD program — and it is exactly Σ_rows of the epilogue gradient."""
    from repro.core.engine import epilogue_grad

    csr, dense = random_csr(rng, 96, density=0.1, skew=True)
    dim = 12
    B = jnp.asarray(rng.standard_normal((96, dim)), jnp.float32)
    sc = jnp.asarray(rng.random(96) + 0.5, jnp.float32)
    b = jnp.asarray(rng.standard_normal(dim), jnp.float32)
    g = DistGraph(csr, dim, 2)
    out = g.fused(B, scale=sc, bias=b, activation="relu")
    dOut = jnp.ones_like(out)
    # the folded program returns BOTH gradients from one SPMD call
    dB, dbias = g._fused_bwd("relu")(out, sc, dOut)
    assert dB.shape == B.shape and dbias.shape == (dim,)
    ref_dbias = epilogue_grad(out, dOut, "relu").sum(axis=0)
    np.testing.assert_allclose(np.asarray(dbias), np.asarray(ref_dbias),
                               rtol=2e-4, atol=2e-5)
    # and the public grad path routes through it
    gbias = jax.grad(lambda bb: g.fused(B, scale=sc, bias=bb,
                                        activation="relu").sum())(b)
    np.testing.assert_allclose(np.asarray(gbias), np.asarray(ref_dbias),
                               rtol=2e-3, atol=2e-4)


@needs_mesh
def test_dist_train_gnn_multihead_gat():
    from repro.apps.gnn import train_gnn
    from repro.data.tasks import community_task

    task = community_task(n_blocks=4, block_size=32, seed=0)
    res = train_gnn(task, model="gat", hidden=16, n_layers=2, steps=6,
                    heads=2, partitions=2)
    assert isinstance(res.config, list) and len(res.config) == 2
    assert res.losses[-1] < res.losses[0]


# --------------------------------------- dynamic per-shard refresh (PR 9)
def _mutate_shard_rows(csr, part, rng, shard, n_new=10):
    """Add edges whose rows AND columns live inside one shard's row
    range — only that shard's local edge slice changes, and no halo can
    grow."""
    lo, hi = int(part.starts[shard]), int(part.starts[shard + 1])
    A = csr.to_dense()
    r = rng.integers(lo, hi, n_new)
    c = rng.integers(lo, hi, n_new)
    A[r, c] = rng.random(n_new).astype(np.float32) + 0.5
    return CSRMatrix.from_dense(A.astype(np.float32))


def test_refresh_reuses_unchanged_shards_identity(rng):
    """Host-side plan contract: a mutation confined to one shard leaves
    every other shard's Shard AND PCSR objects identity-preserved, the
    partition boundaries pinned, and the padded shapes unchanged."""
    csr = rmat(8, 6, seed=11)
    g = DistGraph(csr, 16, 4, strategy="balanced")
    old_shards = list(g.part.shards)
    old_pcsrs = list(g._fwd.pcsrs)
    old_starts = g.part.starts.copy()
    old_shape = (g.part.rows_pad, g.part.halo_pad)
    new_csr = _mutate_shard_rows(csr, g.part, rng, shard=1)
    rep = g.refresh(new_csr)
    assert rep.changed == [1]
    assert set(rep.reused) == {0, 2, 3}
    assert not rep.halo_pad_grew
    for p in rep.reused:
        assert g.part.shards[p] is old_shards[p]       # identity, not copy
        assert g._fwd.pcsrs[p] is old_pcsrs[p]
    assert g._fwd.pcsrs[1] is not old_pcsrs[1]
    np.testing.assert_array_equal(g.part.starts, old_starts)
    assert (g.part.rows_pad, g.part.halo_pad) == old_shape
    assert g.csr is new_csr
    # node set is fixed — a different row count is a re-partition, not
    # a refresh
    with pytest.raises(ValueError, match="fixed node set"):
        g.refresh(rmat(7, 6, seed=1))


def test_shard_drift_reports_changed_shards_only(rng):
    from repro.dynamic import shard_drift

    csr = rmat(8, 6, seed=3)
    g = DistGraph(csr, 16, 4, strategy="balanced")
    assert shard_drift(g, csr) == {}               # no change → no entries
    new_csr = _mutate_shard_rows(csr, g.part, rng, shard=2, n_new=6)
    out = shard_drift(g, new_csr)
    assert set(out) == {2}                         # only the mutated shard
    # a tight threshold turns the entry into a real advisory
    out_tight = shard_drift(g, new_csr, threshold=1e-6)
    assert out_tight[2] is not None and out_tight[2].drifted


@needs_mesh
def test_refresh_dist_spmm_matches_engine_after_mutation(rng):
    """End-to-end per-shard self-healing: after refresh the SPMD SpMM
    matches the single-device engine on the MUTATED graph, including a
    drift-triggered per-shard config re-pick observed via obs counters."""
    from repro import obs

    csr = rmat(8, 7, seed=9)
    dim = 16
    g = DistGraph(csr, dim, 4, strategy="balanced")
    _ = dist_spmm(g, jnp.zeros((csr.n_rows, dim), jnp.float32))  # warm
    new_csr = _mutate_shard_rows(csr, g.part, rng, shard=0, n_new=40)
    obs.reset_metrics()
    with obs.tracing():
        rep = g.refresh(new_csr, threshold=1e-6)   # force the re-pick path
        snap = obs.metrics_snapshot()
    obs.stop_tracing()
    assert rep.changed == [0] and rep.repicked == [0]
    assert 0 in rep.advisories
    assert sum(snap["dist_shard_repacks_total"].values()) == 1
    B = jnp.asarray(rng.standard_normal((csr.n_rows, dim)), jnp.float32)
    cfg, _ = CostModel(new_csr).best(dim, config_space(dim))
    ref = engine_spmm(build_pcsr(new_csr.indptr, new_csr.indices,
                                 new_csr.data, new_csr.n_rows,
                                 new_csr.n_cols, cfg), B)
    _dist_tol(dist_spmm(g, B), ref)
    # grads flow through the refreshed transpose path too
    gd = jax.grad(lambda b: (dist_spmm(g, b) ** 2).sum())(B)
    t = new_csr.transpose()
    pt = build_pcsr(t.indptr, t.indices, t.data, t.n_rows, t.n_cols, cfg)
    ref_fn = make_spmm_fn(build_pcsr(new_csr.indptr, new_csr.indices,
                                     new_csr.data, new_csr.n_rows,
                                     new_csr.n_cols, cfg), pt)
    gr = jax.grad(lambda b: (ref_fn(b) ** 2).sum())(B)
    np.testing.assert_allclose(np.asarray(gd), np.asarray(gr),
                               rtol=2e-3, atol=2e-4)


@needs_mesh
def test_refresh_overlap_mode_rebuilds_changed_split_packs(rng):
    csr = rmat(8, 6, seed=21)
    dim = 12
    g = DistGraph(csr, dim, 4, strategy="balanced", overlap=True)
    _ = dist_spmm(g, jnp.zeros((csr.n_rows, dim), jnp.float32))
    old_loc = list(g._loc.pcsrs)
    new_csr = _mutate_shard_rows(csr, g.part, rng, shard=3, n_new=12)
    rep = g.refresh(new_csr)
    assert rep.changed == [3]
    for p in rep.reused:
        assert g._loc.pcsrs[p] is old_loc[p]
    assert g._loc.pcsrs[3] is not old_loc[3]
    B = jnp.asarray(rng.standard_normal((csr.n_rows, dim)), jnp.float32)
    cfg, _ = CostModel(new_csr).best(dim, config_space(dim))
    ref = engine_spmm(build_pcsr(new_csr.indptr, new_csr.indices,
                                 new_csr.data, new_csr.n_rows,
                                 new_csr.n_cols, cfg), B)
    _dist_tol(dist_spmm(g, B), ref)
