"""Dense operands wider than one 128-lane tile through the Pallas kernels
(interpreter): a 3-layer GCN at OGB's ogbn-arxiv widths, 128 → 256 → 256 →
40, through ``ParamSpMM`` with ``F`` = 1 (two dim tiles at 256) and ``F``
= 2 forced, against a plain gather + ``segment_sum`` reference in float32;
a 40-wide operand under a 256-lane pick; each call's lane tile narrowed to
its operand (``SpMMConfig.dblk_for``); and the ``kernel_gather_lanes_total``,
``spmm_dblk_narrowed_total`` and ``spmm_heads_packed_total`` counters."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import make_spmm_fn
from repro.core.pcsr import LANES, SpMMConfig, build_pcsr, transpose_pcsr
from repro.core.sparse import CSRMatrix
from repro.kernels.paramspmm.kernel import paramspmm_kernel
from repro.kernels.paramspmm.ops import paramspmm
from repro.models.gnn import gcn_forward, node_ce_loss
from repro.obs import metrics_snapshot, reset_metrics
from repro.optim import AdamWConfig, adamw_init, adamw_update
from repro.pipeline import ParamSpMM

from conftest import random_csr

DIMS = [128, 256, 256, 40]          # OGB's arxiv GCN: 3 layers of 256
N = 48
OPT = AdamWConfig(lr=0.01)
HIGHEST = jax.lax.Precision.HIGHEST


def _graph(seed=3):
    """A symmetric random graph, GCN-normalised (self loops included)."""
    rng = np.random.default_rng(seed)
    A = np.triu(rng.random((N, N)) < 0.12, 1)
    A = (A | A.T).astype(np.float32)
    return CSRMatrix.from_dense(A).gcn_normalize(), rng


def _segment_spmm(csr):
    """Â·H by gathering H's rows and summing them per output row."""
    rows = jnp.asarray(np.repeat(np.arange(csr.n_rows), np.diff(csr.indptr)))
    cols = jnp.asarray(csr.indices)
    vals = jnp.asarray(csr.data)
    return lambda H: jax.ops.segment_sum(vals[:, None] * H[cols], rows,
                                         num_segments=csr.n_rows)


def _reference_forward(params, X, agg):
    """relu(Â·H·W + b) per layer, no ReLU after the last, at float32."""
    h = X
    for i, layer in enumerate(params):
        h = jnp.matmul(agg(h), layer["w"], precision=HIGHEST) + layer["b"]
        if i < len(params) - 1:
            h = jax.nn.relu(h)
    return h


def _reference_adam(params, grads):
    """One Adam step from zero moments: both bias corrections are exact,
    so the update is lr · g / (|g| + eps)."""
    return jax.tree.map(
        lambda p, g: p - OPT.lr * g / (jnp.abs(g) + OPT.eps), params, grads)


def _params(rng):
    return [{"w": jnp.asarray(rng.standard_normal((a, b)) * np.sqrt(2 / a),
                              jnp.float32),
             "b": jnp.asarray(rng.standard_normal(b) * 0.1, jnp.float32)}
            for a, b in zip(DIMS[:-1], DIMS[1:])]


@pytest.mark.parametrize("F", [1, 2])
def test_gcn_at_arxiv_widths_matches_reference(F):
    """Logits, every leaf's gradient and one AdamW step of the program's
    GCN (fused epilogues on the 256- and 40-wide layers) against the
    reference.  With F = 1 the 256-wide aggregations run two 128-lane dim
    tiles; with F = 2 they gather 256-lane rows, and the 128- and 40-wide
    ones narrow to 128-lane rows."""
    csr, rng = _graph()
    op = ParamSpMM(csr, 256, config=SpMMConfig(V=2, S=True, F=F, W=4),
                   reorder=False, backend="pallas", interpret=True)
    assert op.config.dblk == F * LANES
    ref = _segment_spmm(csr)
    params = _params(rng)
    X = jnp.asarray(rng.standard_normal((N, DIMS[0])), jnp.float32)
    y = jnp.asarray(rng.integers(0, DIMS[-1], N), jnp.int32)
    mask = jnp.asarray(rng.random(N) < 0.5, jnp.float32)

    def loss(fwd, agg):
        return lambda p: node_ce_loss(fwd(p, X, agg), y, mask)

    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda p: gcn_forward(p, X, op))(params)
        want = _reference_forward(params, X, ref)
        g_got = jax.jit(jax.grad(loss(gcn_forward, op)))(params)
        g_want = jax.grad(loss(_reference_forward, ref))(params)
        stepped, _ = adamw_update(params, g_got, adamw_init(params), OPT)
    # float32 sums of at most ~15 products per row, in another order and
    # association (the program aggregates Â·(H·W) where it fuses): a few
    # ulps of values of order 1
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    for a, b in zip(jax.tree.leaves(g_got), jax.tree.leaves(g_want)):
        # the same rounding, carried back through three layers; scaled by
        # the leaf's largest entry, since entries near 0 carry no digits
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0,
                                   atol=1e-5 * float(jnp.max(jnp.abs(b))))
    for a, b, g in zip(jax.tree.leaves(stepped),
                       jax.tree.leaves(_reference_adam(params, g_want)),
                       jax.tree.leaves(g_want)):
        # the first Adam step moves each entry by lr·g/(|g|+eps): ±lr up
        # to float32 rounding of the parameter wherever |g| stands clear of
        # the gradient's rounding noise (the 1e-5 of its largest entry
        # allowed above); under 1e-3 of it, the noise steers the step, and
        # where it is exactly 0 (a unit no training node reaches) the step
        # is exactly 0
        g = np.abs(np.asarray(g))
        clear = (g >= 1e-3 * g.max()) | (g == 0)
        assert clear.mean() > 0.8
        np.testing.assert_allclose(np.asarray(a)[clear], np.asarray(b)[clear],
                                   rtol=0, atol=1e-6)


@pytest.mark.parametrize("activation", ["none", "relu"])
def test_narrow_operand_under_wide_pack(rng, activation):
    """A 40-wide operand under a ``Dblk`` = 256 pick, narrowed to 128
    lanes: 88 of them are padding and must not reach the output."""
    csr, A = random_csr(rng, 40, 0.15, skew=True)
    p = build_pcsr(csr.indptr, csr.indices, csr.data, 40, 40,
                   SpMMConfig(V=2, S=True, F=2, W=4, B=True))
    B = rng.standard_normal((40, 40)).astype(np.float32)
    bias = rng.standard_normal(40).astype(np.float32)
    out = paramspmm(p, B, bias=bias, activation=activation, interpret=True)
    want = A @ B + bias
    if activation == "relu":
        want = np.maximum(want, 0.0)
    # float32 sums of a few products; padding lanes would add whole values
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dim,F", [(40, 2), (128, 1), (256, 1), (256, 2),
                                   (500, 2)])
def test_gather_lanes_counter(dim, F):
    """Tracing one call adds C·J·K·Dblk lanes moved and C·K·dim carried
    to ``kernel_gather_lanes_total{kernel=<its name>}``."""
    C, V, W, K, n_blocks, n = 3, 2, 4, 16, 2, 32
    dblk = F * LANES
    J = -(-dim // dblk)
    i32, f32 = jnp.int32, jnp.float32
    sd = jax.ShapeDtypeStruct
    args = [sd((C * K,), i32), sd((C * K,), i32), sd((C,), i32),
            sd((C,), i32), sd((C,), i32), sd((C, V, K), f32),
            sd((n, J * dblk), f32)]
    reset_metrics()
    jax.eval_shape(lambda *a: paramspmm_kernel(
        *a, n_blocks=n_blocks, R=V * W, V=V, K=K, dblk=dblk, width=dim,
        interpret=True), *args)
    name = f"paramspmm_v{V}_k{K}_r{V * W}_d{dblk}"
    assert metrics_snapshot()["kernel_gather_lanes_total"] == {
        f"kernel={name},lanes=moved": C * J * K * dblk,
        f"kernel={name},lanes=carried": C * K * dim}


@pytest.mark.parametrize("shape", [(40, 64), (40, 200), (1, 40, 8),
                                   (8, 40, 8), (8, 40, 3), (4, 40, 40)])
def test_heads_packed_counter(rng, shape):
    """Tracing a call whose (H, n, d) operand packs several heads into one
    lane tile, (8, n, 8) and (8, n, 3), adds 1 to
    ``spmm_heads_packed_total{kernel=<its _h8 name>}`` and counts its
    H·d lanes carried over one head's chunks; a 2-D operand, one head,
    or heads wider than the tile (4 × 40) add nothing."""
    csr, _ = random_csr(rng, 40, 0.15)
    p = build_pcsr(csr.indptr, csr.indices, csr.data, 40, 40,
                   SpMMConfig(V=2, S=True, W=4))
    jax.clear_caches()              # trace the call anew, so it counts
    reset_metrics()
    jax.eval_shape(lambda b: paramspmm(p, b, interpret=True),
                   jax.ShapeDtypeStruct(shape, jnp.float32))
    snap = metrics_snapshot()
    if len(shape) == 2 or shape[0] == 1 or shape[0] * shape[2] > LANES:
        assert "spmm_heads_packed_total" not in snap
        return
    H, _, d = shape
    name = f"paramspmm_v2_k{p.K}_r8_d{LANES}_h{H}"
    assert snap["spmm_heads_packed_total"] == {f"kernel={name}": 1}
    C = p.steering(covered=True)["trow"].shape[0]
    assert snap["kernel_gather_lanes_total"] == {
        f"kernel={name},lanes=moved": C * p.K * LANES,
        f"kernel={name},lanes=carried": C * p.K * H * d}


@pytest.mark.parametrize("width,form,tile,J", [
    (40, "plain", 128, 1), (40, "bias", 128, 1), (40, "relu", 128, 1),
    (40, "grad", 128, 1), (128, "plain", 128, 1), (128, "bias", 128, 1),
    (128, "relu", 128, 1), (128, "grad", 128, 1), (256, "plain", 256, 1),
    (300, "plain", 256, 2)])
def test_dim_tile_narrows_to_operand(rng, width, form, tile, J):
    """Under an F = 2 pick each call gathers ``tile`` lanes in J dim tiles:
    the 40- and 128-wide operands dispatch a ``_d128`` form (the transpose-
    PCSR backward of ``jax.grad`` too), 256 keeps ``_d256`` and 300 two
    ``_d256`` tiles.  The F = 2 and F = 1 packs of one graph have the same
    steering arrays, F setting only the kernel's tile, so a narrowed call
    is the F = 1 pack's call, to the bit."""
    A = ((rng.random((40, 56)) < 0.15)
         * rng.standard_normal((40, 56))).astype(np.float32)
    csr = CSRMatrix.from_dense(A)
    packs = {F: build_pcsr(csr.indptr, csr.indices, csr.data, 40, 56,
                           SpMMConfig(V=2, S=True, F=F, W=4, B=True))
             for F in (2, 1)}
    steer = [p.steering(covered=True) for p in packs.values()]
    assert steer[0].keys() == steer[1].keys()
    for k in steer[0]:
        np.testing.assert_array_equal(steer[0][k], steer[1][k])
    B = rng.standard_normal((56, width)).astype(np.float32)
    bias = rng.standard_normal(width).astype(np.float32)
    G = rng.standard_normal((40, width)).astype(np.float32)

    def run(p, pt):
        if form == "grad":
            f = make_spmm_fn(p, pt, backend="pallas", interpret=True)
            return jax.grad(lambda b: jnp.sum(f(b) * G))(B)
        kw = {"plain": {}, "bias": {"bias": bias},
              "relu": {"activation": "relu"}}[form]
        return paramspmm(p, B, interpret=True, **kw)

    jax.clear_caches()              # trace every call anew, so each counts
    reset_metrics()
    pt = transpose_pcsr(packs[2])   # 56 rows: its own shapes, its own trace
    got = run(packs[2], pt)
    snap = metrics_snapshot()
    lanes, narrowed = {}, {}
    for p in [packs[2]] + ([pt] if form == "grad" else []):
        C = p.steering(covered=True)["trow"].shape[0]
        name = (f"paramspmm_v2_k{p.K}_r8_d{tile}"
                f"{'_relu' if form == 'relu' else ''}")
        for kind, n in (("moved", C * J * p.K * tile),
                        ("carried", C * p.K * width)):
            key = f"kernel={name},lanes={kind}"
            lanes[key] = lanes.get(key, 0) + n
        if tile < 2 * LANES:
            narrowed[f"kernel={name}"] = narrowed.get(f"kernel={name}", 0) + 1
    assert snap["kernel_gather_lanes_total"] == lanes
    assert snap.get("spmm_dblk_narrowed_total", {}) == narrowed

    same = run(packs[1], transpose_pcsr(packs[1]))
    if tile == LANES:               # the very kernel the F = 1 pick runs
        np.testing.assert_array_equal(np.asarray(got), np.asarray(same))
    else:                           # lanes independent: same sums per lane
        np.testing.assert_allclose(np.asarray(got), np.asarray(same),
                                   rtol=1e-6, atol=1e-6)
    if form == "grad":
        want = A.T @ G
    else:
        want = A @ B + (bias if form == "bias" else 0)
        if form == "relu":
            want = np.maximum(want, 0.0)
    # float32 sums of a few products, in another order than numpy's
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)
