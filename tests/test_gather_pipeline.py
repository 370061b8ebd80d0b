"""The kernels' two-slot row gather: every kernel form against its ``ref.py``
oracle (Pallas interpreter) where the next grid step crosses a dim tile,
where the call has a single chunk, and where consecutive chunks share an
output block; and the ``kernel_gather_prefetched_share`` gauge."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.pcsr import SpMMConfig, build_pcsr
from repro.kernels.paramspmm import spmm_ref
from repro.kernels.paramspmm.kernel import paramspmm_kernel
from repro.kernels.paramspmm.ops import paramspmm, paramspmm_with_vals
from repro.kernels.sddmm.kernel import sddmm_kernel, sddmm_softmax_kernel
from repro.kernels.sddmm.ops import pack_stats, sddmm, sddmm_softmax
from repro.kernels.sddmm.ref import sddmm_dense_ref
from repro.obs import metrics_snapshot, reset_metrics

from conftest import random_csr

# name → (n, density, skewed rows, config, width)
SHAPES = {
    # width 200 at F=1: two 128-lane dim tiles, so the SpMM's next step
    # after the last chunk and the SDDMM's next step within a chunk both
    # move to another lane tile
    "dim_tiles": (40, 0.15, False, SpMMConfig(V=2, S=False, W=8, F=1), 200),
    # one block, one chunk: C = 1
    "one_chunk": (8, 0.5, False, SpMMConfig(V=1, S=False, W=8, F=1), 64),
    # heavy rows split over chunks that target the same output block
    "split_rows": (48, 0.1, True, SpMMConfig(V=1, S=True, W=8, F=1), 64),
}
FORMS = ["spmm", "spmm_prologue", "spmm_epilogue", "spmm_residual", "sddmm",
         "sddmm_softmax"]


def _case(shape):
    n, density, skew, cfg, dim = SHAPES[shape]
    rng = np.random.default_rng(7)
    csr, A = random_csr(rng, n, density, skew=skew)
    p = build_pcsr(csr.indptr, csr.indices, csr.data, n, n, cfg)
    trow = np.asarray(p.steering(covered=True)["trow"])
    if shape == "one_chunk":
        assert trow.shape[0] == 1
    if shape == "split_rows":
        assert np.any(trow[1:] == trow[:-1])
    B = rng.standard_normal((n, dim)).astype(np.float32)
    return rng, csr, A, p, B


def _slots_to_dense(p, slots):
    """Scatter a (C, V, K) slot tensor onto the dense (n, n) pattern; only
    real slots (nonzero stored value) are written."""
    cfg, K = p.config, p.K
    vals = np.asarray(p.vals)
    slots = np.asarray(slots)[:vals.shape[0]]
    out = np.zeros((p.n_rows, p.n_cols), np.float32)
    c, v, k = np.nonzero(vals)
    rows = (np.asarray(p.trow)[c] * cfg.R
            + np.asarray(p.lrow)[c * K + k] * cfg.V + v)
    out[rows, np.asarray(p.colidx)[c * K + k]] = slots[c, v, k]
    return out


def _row_softmax(L, mask):
    """Per-row max and Σexp of ``L`` over ``mask``, and the weights."""
    Lm = np.where(mask, L, -np.inf)
    m = Lm.max(axis=1)
    e = np.where(mask, np.exp(Lm - np.where(np.isfinite(m), m, 0)[:, None]),
                 0.0)
    s = e.sum(axis=1)
    return m, s, e / np.where(s > 0, s, 1.0)[:, None]


def _spmm_dense(csr, W, B):
    """spmm_ref over the pattern of ``csr`` with the weights of dense W."""
    rows = np.repeat(np.arange(csr.n_rows), np.diff(csr.indptr))
    data = W[rows, np.asarray(csr.indices)]
    return np.asarray(spmm_ref(csr.indptr, csr.indices, data,
                               jnp.asarray(B), csr.n_rows))


@pytest.mark.parametrize("shape", list(SHAPES))
@pytest.mark.parametrize("form", FORMS)
def test_pipelined_gather_matches_oracle(form, shape):
    rng, csr, A, p, B = _case(shape)
    n, dim = B.shape
    ref_ab = np.asarray(spmm_ref(csr.indptr, csr.indices, csr.data,
                                 jnp.asarray(B), n))
    tol = dict(atol=1e-4, rtol=1e-4)
    if form == "spmm":
        out = paramspmm(p, B, interpret=True)
        np.testing.assert_allclose(np.asarray(out), ref_ab, **tol)
    elif form == "spmm_epilogue":
        scale = rng.random(n).astype(np.float32)
        bias = rng.standard_normal(dim).astype(np.float32)
        out = paramspmm(p, B, scale=scale, bias=bias, activation="relu",
                        interpret=True)
        want = np.maximum(scale[:, None] * ref_ab + bias, 0.0)
        np.testing.assert_allclose(np.asarray(out), want, **tol)
    elif form == "spmm_residual":
        resid = rng.standard_normal((n, dim)).astype(np.float32)
        out = paramspmm(p, B, residual=resid, interpret=True)
        np.testing.assert_allclose(np.asarray(out), ref_ab + resid, **tol)
    elif form == "spmm_prologue":
        # logits = the stored edge values, −inf on padding slots; the row
        # stats are the dense softmax's, in the kernel's tile layout
        vals = np.asarray(p.vals)
        logits = np.where(vals != 0, vals, -np.inf).astype(np.float32)
        m, s, alpha = _row_softmax(A, A != 0)
        rows = p.n_blocks * p.config.R
        pad = lambda x, f: np.pad(x, (0, rows - n), constant_values=f)
        stats = tuple(pack_stats(jnp.asarray(pad(x, f)).reshape(
            p.n_blocks, p.config.R), p.config.R)
            for x, f in ((m, -np.inf), (s, 0.0)))
        out = paramspmm_with_vals(p, logits, B, stats=stats, interpret=True)
        np.testing.assert_allclose(np.asarray(out), _spmm_dense(csr, alpha, B),
                                   **tol)
    elif form == "sddmm":
        Kmat = rng.standard_normal((n, dim)).astype(np.float32)
        got = _slots_to_dense(p, sddmm(p, B, Kmat, interpret=True))
        np.testing.assert_allclose(got, sddmm_dense_ref(A, B, Kmat), **tol)
    else:
        assert form == "sddmm_softmax"
        Kmat = rng.standard_normal((n, dim)).astype(np.float32)
        got = _slots_to_dense(p, sddmm_softmax(p, B, Kmat, interpret=True))
        L = sddmm_dense_ref(A, B, Kmat) / np.sqrt(np.float32(dim))
        L = np.where(L >= 0, L, 0.2 * L)
        _, _, alpha = _row_softmax(L, A != 0)
        np.testing.assert_allclose(got, alpha, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("C,J", [(5, 2), (3, 1), (1, 1)])
@pytest.mark.parametrize("kernel", ["paramspmm", "sddmm", "sddmm_softmax"])
def test_prefetched_share_gauge(kernel, C, J):
    """Tracing a call sets kernel_gather_prefetched_share{kernel=<name>}
    to (C·J − 1)/(C·J): every grid step but the first found its rows in
    flight."""
    V, W, K, dblk = 2, 4, 16, 128
    R, n_blocks, n = V * W, 2, 32
    i32, f32 = jnp.int32, jnp.float32
    sd = jax.ShapeDtypeStruct
    steer = [sd((C * K,), i32), sd((C * K,), i32), sd((C,), i32),
             sd((C,), i32), sd((C,), i32)]
    vals, X = sd((C, V, K), f32), sd((n, J * dblk), f32)
    Q = sd((n_blocks * R, J * dblk), f32)
    if kernel == "paramspmm":
        fn = lambda *a: paramspmm_kernel(*a, n_blocks=n_blocks, R=R, V=V,
                                         K=K, dblk=dblk, interpret=True)
        args, name = steer + [vals, X], f"paramspmm_v{V}_k{K}_r{R}_d{dblk}"
    elif kernel == "sddmm":
        fn = lambda *a: sddmm_kernel(*a, W=W, V=V, K=K, dblk=dblk,
                                     interpret=True)
        args, name = steer[:3] + [Q, X], f"sddmm_v{V}_k{K}_w{W}_d{dblk}"
    else:
        fn = lambda *a: sddmm_softmax_kernel(
            *a, n_blocks=n_blocks, W=W, V=V, K=K, dblk=dblk, scale=1.0,
            slope=0.2, interpret=True)
        args = steer[:4] + [vals, Q, X]
        name = f"sddmm_softmax_v{V}_k{K}_w{W}_d{dblk}"
    reset_metrics()
    jax.eval_shape(fn, *args)
    share = metrics_snapshot()["kernel_gather_prefetched_share"]
    assert share == {f"kernel={name}": (C * J - 1) / (C * J)}
