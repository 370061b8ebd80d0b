"""Compile the main-path kernels for a described TPU v5e, at the smoke
graph's real steering sizes, without a chip.

The smoke graph (``chip_smoke.py``: 65,536 nodes, 1.47M nonzeros after
GCN normalization) packs under its picked ⟨W=16, F=1, V=2, S, B⟩ into
2,066 chunks of K = 776 slots — 1,603,216 slots — over 2,048 output
blocks of R = 32 rows.  Every kernel form of the GCN/GIN/GAT path is
compiled at that size, single-head and 4-head tiled, the SpMM forms
also over a 256-wide operand (256-lane rows, or two 128-lane dim tiles),
and the attention SpMM forms with 4 and 8 heads of 8 packed into lanes;
then whole jitted GCN value-and-grad steps, at the smoke widths and at
OGB's ogbn-arxiv widths, whose device memory must fit a v5e.

The topology is described inside a module fixture, never at import: only
the test worker that runs this file loads the TPU compiler.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.pcsr import LANES, SUBLANES

C, K, V, W, DBLK, N = 2066, 776, 2, 16, 128, 65536
R, NB = V * W, 2048
V5E_HBM_BYTES = 16 * 1024**3


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                       # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


# a wide form's suffix → (Dblk, operand width): 256-lane rows (F = 2),
# or two 128-lane dim tiles (F = 1, J = 2), of a 256-wide operand
WIDE = {"_wide_f2": (2 * LANES, 256), "_wide_j2": (LANES, 256)}
HEAD_DIM = 8            # the packed forms' heads, as in the GAT cell's

# (form, H): every form single-head and 4-head tiled; the packed forms
# with 4 and 8 heads
FORMS = [(name, H) for name in (
    "spmm", "spmm_epilogue", "spmm_residual", "spmm_softmax_prologue",
    "sddmm_softmax", "sddmm", "spmm_wide_f2", "spmm_epilogue_wide_f2",
    "spmm_wide_j2", "spmm_epilogue_wide_j2") for H in (1, 4)] + [
    (name, H) for name in ("spmm_packed", "spmm_softmax_prologue_packed")
    for H in (4, 8)]


def _packed_form(name: str, H: int, sd):
    """(fn, argument shapes) of an attention SpMM form with H heads of
    ``HEAD_DIM`` packed into the lanes of one row, over one copy of the
    smoke graph's steering."""
    from repro.kernels.paramspmm.kernel import paramspmm_kernel
    f32, i32 = jnp.float32, jnp.int32
    steer = [sd((C * K,), i32), sd((C * K,), i32), sd((C,), i32),
             sd((C,), i32), sd((C,), i32)]
    args = steer + [sd((C, V, K, H), f32), sd((N, DBLK), f32)]
    kw = dict(n_blocks=NB, R=R, V=V, K=K, dblk=DBLK, width=H * HEAD_DIM,
              heads=H, interpret=False)
    if name == "spmm_packed":          # GAT backward dQ, dK, dVf
        return (lambda *a: paramspmm_kernel(*a, **kw)), args
    assert name == "spmm_softmax_prologue_packed"    # GAT aggregation
    return (lambda *a: paramspmm_kernel(*a[:7], rowmax=a[7], rowsum=a[8],
                                        **kw),
            args + [sd((NB * R, DBLK), f32)] * 2)


def _form(name: str, H: int, sd):
    """(fn, argument shapes) of one kernel form over H head-tiled
    copies of the smoke graph's steering."""
    from repro.kernels.paramspmm.kernel import paramspmm_kernel
    from repro.kernels.sddmm.kernel import sddmm_kernel, sddmm_softmax_kernel
    if name.endswith("_packed"):
        return _packed_form(name, H, sd)
    c, nb, n = H * C, H * NB, H * N
    f32, i32 = jnp.float32, jnp.int32
    steer = [sd((c * K,), i32), sd((c * K,), i32), sd((c,), i32),
             sd((c,), i32), sd((c,), i32)]
    wide = next((w for w in WIDE if name.endswith(w)), "")
    dblk, width = WIDE.get(wide, (DBLK, DBLK))
    name = name.removesuffix(wide)
    vals, B = sd((c, V, K), f32), sd((n, width), f32)
    stats = sd((nb * SUBLANES, LANES), f32)
    kw = dict(n_blocks=nb, R=R, V=V, K=K, dblk=dblk, interpret=False)
    if name == "spmm":
        return (lambda *a: paramspmm_kernel(*a, **kw)), steer + [vals, B]
    if name == "spmm_epilogue":        # GCN: degree scale + bias + ReLU
        return (lambda *a: paramspmm_kernel(
            *a[:7], scale=a[7], bias=a[8], activation="relu", **kw),
            steer + [vals, B, sd((nb * R, 1), f32),
                     sd((SUBLANES, width), f32)])
    if name == "spmm_residual":        # GIN: (1+ε)h + A·h
        return (lambda *a: paramspmm_kernel(*a[:7], residual=a[7], **kw),
                steer + [vals, B, sd((nb * R, DBLK), f32)])
    if name == "spmm_softmax_prologue":  # GAT aggregation
        return (lambda *a: paramspmm_kernel(
            *a[:7], rowmax=a[7], rowsum=a[8], **kw),
            steer + [vals, B, stats, stats])
    if name == "sddmm_softmax":        # GAT scores + row stats
        return (lambda *a: sddmm_softmax_kernel(
            *a, n_blocks=nb, W=W, V=V, K=K, dblk=DBLK, scale=0.25,
            slope=0.2, interpret=False),
            steer[:4] + [vals, sd((nb * R, DBLK), f32), B])
    assert name == "sddmm"             # GAT backward dα
    return (lambda *a: sddmm_kernel(*a, W=W, V=V, K=K, dblk=DBLK,
                                    interpret=False),
            steer[:3] + [sd((nb * R, DBLK), f32), B])


@pytest.mark.parametrize("name,H", FORMS)
def test_kernel_compiles_for_v5e(one_chip, name, H):
    """Each form compiles with its two-slot gather, and tracing it sets the
    prefetched-share gauge: every grid step but the first (J = 1, but for
    the ``_wide_j2`` forms' two dim tiles; a packed form's grid is one
    head's chunks).  A wide form gathers a 256-wide operand and counts
    every gathered lane as carrying data; a packed form counts its H·8
    lanes as carried, and itself once as packed."""
    from repro.obs import metrics_snapshot, reset_metrics
    sd = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    fn, args = _form(name, H, sd)
    J = 2 if name.endswith("_wide_j2") else 1
    steps = (1 if name.endswith("_packed") else H) * C * J
    reset_metrics()
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo
    snap = metrics_snapshot()
    assert list(snap["kernel_gather_prefetched_share"].values()) == [
        (steps - 1) / steps]
    if "_wide_" in name:
        lanes = snap["kernel_gather_lanes_total"]
        assert sorted(lanes.values()) == [H * C * K * 256] * 2
    if name.endswith("_packed"):
        (kernel,) = snap["spmm_heads_packed_total"]
        assert kernel.endswith(f"_h{H}")
        assert snap["spmm_heads_packed_total"][kernel] == 1
        assert sorted(snap["kernel_gather_lanes_total"].values()) == [
            C * K * H * HEAD_DIM, C * K * DBLK]
    else:
        assert "spmm_heads_packed_total" not in snap


def test_gcn_train_step_compiles_and_fits_v5e(one_chip):
    """One whole jitted GCN value-and-grad step (configs/gcn.py CONFIG)
    on the smoke graph through the default ParamSpMM operator: kernels
    in the HLO, device memory within a v5e's 16 GB."""
    from repro.configs.gcn import CONFIG
    from repro.data.tasks import community_task
    from repro.models.gnn import gcn_forward, init_gcn, node_ce_loss
    from repro.pipeline import ParamSpMM

    task = community_task(n_blocks=16, block_size=4096, p_in=0.005)
    op = ParamSpMM(task.csr.gcn_normalize(), CONFIG["hidden"],
                   backend="pallas", interpret=False)
    p = op.op.pcsr
    assert (p.num_chunks, p.K, p.config.V, p.config.W) == (C, K, V, W)
    dims = ([CONFIG["in_dim"]] + [CONFIG["hidden"]] * (CONFIG["n_layers"] - 1)
            + [CONFIG["out_dim"]])
    params = init_gcn(jax.random.PRNGKey(0), dims)
    sd = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)
    n = task.csr.n_rows
    args = (jax.tree.map(sd, params), sd(jnp.zeros((n, CONFIG["in_dim"]))),
            sd(jnp.zeros(n, jnp.int32)), sd(jnp.zeros(n)))

    def loss(params, X, labels, mask):
        return node_ce_loss(gcn_forward(params, X, op), labels, mask)

    compiled = jax.jit(jax.value_and_grad(loss)).lower(*args).compile()
    # forward: 5 aggregations; backward: 4 transpose aggregations (the
    # first layer's input needs no gradient)
    assert compiled.as_text().count("tpu_custom_call") >= 9
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes + mem.generated_code_size_in_bytes)
    assert used < V5E_HBM_BYTES


def test_gcn_arxiv_widths_train_step_compiles_and_fits_v5e(one_chip):
    """The same step at OGB's ogbn-arxiv GCN widths, 128 → 256 → 256 → 40,
    through ``ParamSpMM`` built for width 256 (F = 2): the 256-wide
    aggregations, forward and backward, run kernels gathering 256-lane
    rows, and the 128- and 40-wide ones narrow to 128-lane rows; device
    memory within 16 GB."""
    from repro.core.pcsr import SpMMConfig
    from repro.data.tasks import community_task
    from repro.models.gnn import gcn_forward, init_gcn, node_ce_loss
    from repro.obs import metrics_snapshot, reset_metrics
    from repro.pipeline import ParamSpMM

    dims = [128, 256, 256, 40]
    task = community_task(n_blocks=16, block_size=4096, p_in=0.005)
    op = ParamSpMM(task.csr.gcn_normalize(), dims[1],
                   config=SpMMConfig(V=V, S=True, F=2, W=W, B=True),
                   backend="pallas", interpret=False)
    params = init_gcn(jax.random.PRNGKey(0), dims)
    sd = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)
    n = task.csr.n_rows
    args = (jax.tree.map(sd, params), sd(jnp.zeros((n, dims[0]))),
            sd(jnp.zeros(n, jnp.int32)), sd(jnp.zeros(n)))

    def loss(params, X, labels, mask):
        return node_ce_loss(gcn_forward(params, X, op), labels, mask)

    jax.clear_caches()          # trace every kernel call anew, so each counts
    reset_metrics()
    compiled = jax.jit(jax.value_and_grad(loss)).lower(*args).compile()
    # forward: 3 aggregations; backward: 2 (the first layer aggregates
    # the input features, which need no gradient)
    assert compiled.as_text().count("tpu_custom_call") >= 5
    snap = metrics_snapshot()
    carried = {k.split(",")[0].removeprefix("kernel="): v
               for k, v in snap["kernel_gather_lanes_total"].items()
               if k.endswith("lanes=carried")}
    # the 256-wide aggregations (layer 2's forward with the ReLU epilogue,
    # and its backward) gather 256 lanes; the 128-wide (layer 1's forward)
    # and 40-wide ones (layer 3's forward with the bias epilogue, and its
    # backward) narrow to 128, three narrowed calls
    assert {k.rsplit("_d", 1)[1] for k in carried} == {
        "128", "256", "256_relu"}, carried
    narrowed = snap["spmm_dblk_narrowed_total"]
    assert {k.removeprefix("kernel=") for k in narrowed} == {
        k for k in carried if k.endswith("_d128")}
    assert sum(narrowed.values()) == 3
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes + mem.generated_code_size_in_bytes)
    assert used < V5E_HBM_BYTES
