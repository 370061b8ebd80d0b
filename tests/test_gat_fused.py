"""Fused GAT attention pipeline: fused SDDMM→softmax kernel vs the
engine oracle, multi-head batching (heads packed into the SpMM's lanes,
or head-tiled), the dedicated transpose-PCSR backward, and the slot
transfer map's round-trip properties."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import (_slot_rows, edge_softmax, engine_sddmm,
                               engine_spmm, make_gat_message_fn)
from repro.core.pcsr import (SpMMConfig, build_pcsr, slot_transfer_map,
                             transpose_pcsr)
from repro.core.sparse import CSRMatrix
from repro.kernels.paramspmm import paramspmm_with_vals
from repro.kernels.sddmm import sddmm_softmax, sddmm_softmax_stats
from repro.obs import metrics_snapshot, reset_metrics

from conftest import random_csr
from _propcheck import booleans, floats, integers, propcases, sampled_from

CONFIGS = [SpMMConfig(V=1, S=False, F=1, W=8),
           SpMMConfig(V=2, S=False, F=2, W=4),
           SpMMConfig(V=1, S=True, F=1, W=16),   # split chunks
           SpMMConfig(V=2, S=True, F=1, W=8)]    # split + vector padding


def _oracle_alpha(p, Q, K, slope=0.2):
    """Unfused reference: engine SDDMM → scale → LeakyReLU → segment
    softmax — the exact pipeline the fused kernel replaces."""
    arrs = p.to_jax()
    cfg = p.config
    scores = engine_sddmm(p, Q, K)
    mask = arrs["vals"] != 0
    rows = _slot_rows(arrs["lrow"], arrs["trow"], V=cfg.V, R=cfg.R, K=p.K)
    scaled = jax.nn.leaky_relu(
        scores / jnp.sqrt(jnp.float32(Q.shape[-1])), negative_slope=slope)
    return np.asarray(edge_softmax(scaled, mask, rows, p.n_blocks * cfg.R))


@pytest.mark.parametrize("cfg", CONFIGS, ids=str)
def test_fused_softmax_matches_engine_oracle(rng, cfg):
    csr, A = random_csr(rng, 67, 0.1)
    Q = rng.standard_normal((67, 40)).astype(np.float32)
    K = rng.standard_normal((67, 40)).astype(np.float32)
    p = build_pcsr(csr.indptr, csr.indices, csr.data, 67, 67, cfg)
    alpha = np.asarray(sddmm_softmax(p, Q, K, interpret=True))
    np.testing.assert_allclose(alpha, _oracle_alpha(p, Q, K),
                               atol=1e-5, rtol=1e-5)


def test_fused_softmax_empty_rows_and_masked_edges(rng):
    # empty-row band + explicit-zero (masked) edges in the stored data
    n = 64
    A = ((rng.random((n, n)) < 0.2)
         * rng.standard_normal((n, n))).astype(np.float32)
    A[8:40] = 0.0
    rows, cols = np.nonzero(A)
    vals = A[rows, cols].copy()
    vals[:: 5] = 0.0                      # every 5th stored edge masked out
    csr = CSRMatrix.from_coo(rows, cols, vals, n, n, sum_duplicates=False)
    Q = rng.standard_normal((n, 24)).astype(np.float32)
    K = rng.standard_normal((n, 24)).astype(np.float32)
    for cfg in (SpMMConfig(V=2, S=True, W=4), SpMMConfig(V=1, S=False, W=8)):
        p = build_pcsr(csr.indptr, csr.indices, csr.data, n, n, cfg)
        alpha = np.asarray(sddmm_softmax(p, Q, K, interpret=True))
        oracle = _oracle_alpha(p, Q, K)
        np.testing.assert_allclose(alpha, oracle, atol=1e-5, rtol=1e-5)
        # masked slots carry exactly zero weight
        assert (alpha[np.asarray(p.vals) == 0] == 0).all()


@pytest.mark.parametrize("case", propcases(
    4, n=integers(8, 50), d=sampled_from([8, 40, 130]),
    density=floats(0.02, 0.3), v=sampled_from([1, 2]),
    s=booleans(), seed=integers(0, 99)), ids=str)
def test_fused_softmax_property(case):
    rng = np.random.default_rng(case.seed)
    csr, _ = random_csr(rng, case.n, case.density)
    Q = rng.standard_normal((case.n, case.d)).astype(np.float32)
    K = rng.standard_normal((case.n, case.d)).astype(np.float32)
    p = build_pcsr(csr.indptr, csr.indices, csr.data, case.n, case.n,
                   SpMMConfig(V=case.v, S=case.s, W=8 // case.v))
    alpha = np.asarray(sddmm_softmax(p, Q, K, interpret=True))
    np.testing.assert_allclose(alpha, _oracle_alpha(p, Q, K),
                               atol=1e-5, rtol=1e-5)


def test_fused_multihead_matches_per_head_and_compiles_once(rng, monkeypatch):
    import repro.kernels.sddmm.kernel as kmod
    csr, _ = random_csr(rng, 41, 0.15)
    H = 4
    Qh = rng.standard_normal((H, 41, 9)).astype(np.float32)
    Kh = rng.standard_normal((H, 41, 9)).astype(np.float32)
    p = build_pcsr(csr.indptr, csr.indices, csr.data, 41, 41,
                   SpMMConfig(V=2, S=True, W=8))
    calls = []
    orig = kmod.sddmm_softmax_kernel
    monkeypatch.setattr(kmod, "sddmm_softmax_kernel",
                        lambda *a, **kw: calls.append(1) or orig(*a, **kw))
    batched = np.asarray(sddmm_softmax(p, Qh, Kh, interpret=True))
    # ≥4 heads, one head-tiled kernel trace — not a per-head loop/vmap
    assert len(calls) == 1
    per_head = np.stack([np.asarray(sddmm_softmax(p, Qh[h], Kh[h],
                                                  interpret=True))
                         for h in range(H)])
    np.testing.assert_allclose(batched, per_head, atol=1e-6, rtol=1e-6)


# (H, d): 8 × 8 = 64 and 8 × 3 = 24 lanes pack into one 128-lane row;
# 4 × 40 = 160 does not, and keeps head tiling
@pytest.mark.parametrize("form", ["vals", "stored", "prologue"])
@pytest.mark.parametrize("H,d", [(8, 8), (8, 3), (4, 40)])
def test_multihead_spmm_matches_per_head(rng, H, d, form):
    """An (H, n, d) SpMM equals each head's own single-head call to the
    bit, heads packed into lanes or not: every head keeps its slots,
    their order and the f32 operations.  Call-time per-head values,
    stored values (every head's) and the softmax prologue's per-head
    logits and stats, over a graph with an empty-row band (coverage
    chunks) and masked edges (−inf logits)."""
    n = 48
    A = ((rng.random((n, n)) < 0.2)
         * rng.standard_normal((n, n))).astype(np.float32)
    A[8:24] = 0.0
    rows, cols = np.nonzero(A)
    vals = A[rows, cols].copy()
    vals[::5] = 0.0                       # every 5th stored edge masked out
    csr = CSRMatrix.from_coo(rows, cols, vals, n, n, sum_duplicates=False)
    p = build_pcsr(csr.indptr, csr.indices, csr.data, n, n,
                   SpMMConfig(V=2, S=True, W=4))
    assert p.n_empty_blocks > 0           # the call appends coverage chunks
    B = rng.standard_normal((H, n, d)).astype(np.float32)
    per_head = [{} for _ in range(H)]
    if form == "vals":
        v = rng.standard_normal((H,) + p.vals.shape).astype(np.float32)
        v[:, np.asarray(p.vals) == 0] = 0.0
        kw = {"vals": v}
        per_head = [{"vals": v[h]} for h in range(H)]
    elif form == "prologue":
        Q = rng.standard_normal((H, n, d)).astype(np.float32)
        K = rng.standard_normal((H, n, d)).astype(np.float32)
        logits, rm, rs = sddmm_softmax_stats(p, Q, K, interpret=True)
        assert np.isneginf(np.asarray(logits)).any()
        kw = {"vals": logits, "stats": (rm, rs)}
        rm, rs = (np.asarray(x).reshape(H, -1, x.shape[-1]) for x in (rm, rs))
        per_head = [{"vals": logits[h], "stats": (rm[h], rs[h])}
                    for h in range(H)]
    else:
        kw = {"vals": None}
    jax.clear_caches()                    # trace every call anew
    reset_metrics()
    got = np.asarray(paramspmm_with_vals(p, kw.pop("vals"), B,
                                         interpret=True, **kw))
    packed = metrics_snapshot().get("spmm_heads_packed_total", {})
    form_name = f"paramspmm_v2_k{p.K}_r8_d128{'_pro' * (form == 'prologue')}"
    assert packed == ({f"kernel={form_name}_h{H}": 1} if H * d <= 128
                      else {})
    want = np.stack([np.asarray(paramspmm_with_vals(
        p, per_head[h].pop("vals", None), B[h], interpret=True,
        **per_head[h])) for h in range(H)])
    assert got.shape == (H, n, d) and np.isfinite(got).all()
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(got[:, 8:24], 0.0)   # empty rows


def test_gat_pallas_backward_no_engine_fallback(rng, monkeypatch):
    """The dedicated backward never touches the engine path."""
    import repro.core.engine as emod
    csr, _ = random_csr(rng, 40, 0.15)
    Q = rng.standard_normal((40, 16)).astype(np.float32)
    K = rng.standard_normal((40, 16)).astype(np.float32)
    Vf = rng.standard_normal((40, 12)).astype(np.float32)
    p = build_pcsr(csr.indptr, csr.indices, csr.data, 40, 40,
                   SpMMConfig(V=2, S=True, W=8))
    f_eng = make_gat_message_fn(p, backend="engine")
    g_eng = jax.grad(lambda q, k, v: (f_eng(q, k, v) ** 2).sum(),
                     argnums=(0, 1, 2))(Q, K, Vf)
    f_pal = make_gat_message_fn(p, backend="pallas", interpret=True)

    def _boom(*a, **kw):
        raise AssertionError("engine fallback in the Pallas GAT path")

    monkeypatch.setattr(emod, "_engine", _boom)
    monkeypatch.setattr(emod, "_engine_sddmm", _boom)
    monkeypatch.setattr(emod, "edge_softmax", _boom)
    g_pal = jax.grad(lambda q, k, v: (f_pal(q, k, v) ** 2).sum(),
                     argnums=(0, 1, 2))(Q, K, Vf)
    for a, b in zip(g_eng, g_pal):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)


def test_gat_multihead_grad_matches_finite_differences(rng):
    """Pallas multi-head backward vs central differences."""
    n, d, H = 18, 4, 4
    csr, _ = random_csr(rng, n, 0.25)
    p = build_pcsr(csr.indptr, csr.indices, csr.data, n, n,
                   SpMMConfig(V=2, S=False, W=4))
    f = make_gat_message_fn(p, backend="pallas", interpret=True)
    Q = rng.standard_normal((H, n, d)).astype(np.float32)
    K = rng.standard_normal((H, n, d)).astype(np.float32)
    Vf = rng.standard_normal((H, n, 3)).astype(np.float32)
    w = jnp.asarray(rng.standard_normal((H, n, 3)), jnp.float32)

    def loss(q, k, v):
        return (f(q, k, v) * w).sum()

    grads = jax.grad(loss, argnums=(0, 1, 2))(Q, K, Vf)
    eps = 1e-3
    for ai, arr in enumerate((Q, K, Vf)):
        g = np.asarray(grads[ai])
        for idx in [(0, 0, 0), (1, 3, 2),
                    (H - 1, arr.shape[1] - 1, arr.shape[2] - 1)]:
            up, dn = arr.copy(), arr.copy()
            up[idx] += eps
            dn[idx] -= eps
            args_u, args_d = [Q, K, Vf], [Q, K, Vf]
            args_u[ai], args_d[ai] = up, dn
            fd = (float(loss(*args_u)) - float(loss(*args_d))) / (2 * eps)
            np.testing.assert_allclose(g[idx], fd, atol=5e-2, rtol=5e-2)


@pytest.mark.parametrize("case", propcases(
    6, n=integers(8, 40), density=floats(0.05, 0.3),
    v=sampled_from([1, 2]), s=booleans(), seed=integers(0, 99)), ids=str)
def test_transpose_pcsr_roundtrip_property(case):
    rng = np.random.default_rng(case.seed)
    csr, A = random_csr(rng, case.n, case.density)
    p = build_pcsr(csr.indptr, csr.indices, csr.data, case.n, case.n,
                   SpMMConfig(V=case.v, S=case.s, W=8 // case.v))
    p_t = transpose_pcsr(p)
    f_idx, t_idx = slot_transfer_map(p, p_t)
    assert f_idx.shape[0] == csr.nnz == t_idx.shape[0]
    # transferring A's stored values lands exactly on Aᵀ-PCSR's own values
    tv = np.zeros(p_t.num_chunks * p_t.config.V * p_t.K, np.float32)
    tv[t_idx] = p.vals.reshape(-1)[f_idx]
    np.testing.assert_array_equal(tv.reshape(p_t.vals.shape), p_t.vals)
    # round-trip: fwd → transpose → fwd recovers an arbitrary slot tensor
    x = np.zeros(p.vals.size, np.float32)
    x[f_idx] = rng.standard_normal(f_idx.shape[0]).astype(np.float32)
    tvx = np.zeros_like(tv)
    tvx[t_idx] = x[f_idx]
    back = np.zeros_like(x)
    back[f_idx] = tvx[t_idx]
    np.testing.assert_array_equal(back, x)
    # and the transpose PCSR really computes Aᵀ·B
    B = rng.standard_normal((case.n, 8)).astype(np.float32)
    np.testing.assert_allclose(np.asarray(engine_spmm(p_t, B)), A.T @ B,
                               atol=1e-4, rtol=1e-4)
