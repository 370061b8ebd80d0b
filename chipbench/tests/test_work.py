"""Work counts from shapes alone: hand-counted values, and the same
counts whatever kernel configuration the program picks."""
import numpy as np
import pytest

from chipbench import work

PEAKS = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}


def test_spmm_and_sddmm_by_hand():
    # n = 4 rows, 10 nonzeros, width 3: 2·10·3 FLOPs; 4 B × (10 indices
    # + 10 values + 5 row pointers) + 4 B × (4·3 in + 4·3 out)
    assert work.spmm_cost(4, 10, 3) == (60.0, 4 * 25 + 4 * 24)
    assert work.sddmm_cost(4, 10, 3) == (60.0, 4 * 25 + 4 * 24)


def test_gcn_step_by_hand():
    m = {"kind": "gcn", "layers": 2, "in_features": 6, "hidden": 4,
         "classes": 2, "heads": 1}
    n, nnz = 5, 13
    w = work.step_work(m, n, nnz)
    # layer 1 (6→4) aggregates 4 wide, forward and backward; layer 2
    # (4→2) aggregates 2 wide, forward and backward
    assert w["spmm"] == [work.spmm_cost(n, nnz, 4)] * 2 + \
        [work.spmm_cost(n, nnz, 2)] * 2
    assert w["sddmm"] == []
    # X·W and Xᵀ·dY for layer 1 (its input needs no gradient), all three
    # products for layer 2
    assert w["dense"] == 2 * 5 * 6 * 4 * 2 + 2 * 5 * 4 * 2 * 3
    assert w["flops"] == w["dense"] + 2 * 13 * (4 + 4 + 2 + 2)


def test_gcn_widening_first_layer_aggregates_input_once():
    m = {"kind": "gcn", "layers": 2, "in_features": 3, "hidden": 8,
         "classes": 2, "heads": 1}
    w = work.step_work(m, 5, 13)
    # 3 → 8 aggregates the 3-wide input, which needs no gradient
    assert w["spmm"] == [work.spmm_cost(5, 13, 3)] + \
        [work.spmm_cost(5, 13, 2)] * 2


def test_dpgat_step_by_hand():
    m = {"kind": "dpgat", "layers": 2, "in_features": 6, "hidden": 8,
         "classes": 3, "heads": 2}
    n, nnz = 5, 13
    w = work.step_work(m, n, nnz)
    # layer 1: 2 heads of 4; layer 2: 2 heads of 3 (averaged)
    assert w["sddmm"] == [work.sddmm_cost(n, nnz, 4)] * 4 + \
        [work.sddmm_cost(n, nnz, 3)] * 4
    assert w["spmm"] == [work.spmm_cost(n, nnz, 4)] * 8 + \
        [work.spmm_cost(n, nnz, 3)] * 8
    assert w["dense"] == 3 * 2 * n * 6 * 8 * 2 + 3 * 2 * n * 8 * 6 * 3


def test_roofline_is_the_larger_bound():
    assert work.least_seconds([(197e12, 1.0)], PEAKS) == pytest.approx(1.0)
    assert work.least_seconds([(1.0, 819e9), (1.0, 819e9)], PEAKS) == \
        pytest.approx(2.0)


def test_counts_do_not_follow_the_kernel_configuration():
    """Two ⟨W,F,V,S,B⟩ picks pack the same graph into different slot
    counts; the work the step is charged stays the same."""
    from types import SimpleNamespace

    from chipbench.graphs import rmat_edges
    from repro.apps.gnn import build_spmm
    from repro.core.pcsr import SpMMConfig
    from repro.core.sparse import CSRMatrix

    u, v = rmat_edges(300, 900, seed=3)
    csr = CSRMatrix.from_edges(u, v, 300, symmetrize=True)
    m = {"kind": "gcn", "layers": 2, "in_features": 16, "hidden": 8,
         "classes": 4, "heads": 1}
    counts, slots = [], []
    for cfg in (SpMMConfig(V=2, S=True, F=1, W=16, B=True),
                SpMMConfig(V=8, S=False, F=2, W=4, B=False)):
        op, _, picked = build_spmm(SimpleNamespace(csr=csr), 8, config=cfg,
                                   backend="engine")
        assert picked == cfg
        p = op.op.pcsr
        slots.append(p.num_chunks * p.K * cfg.V)
        counts.append(work.step_work(m, op.csr.n_rows, op.csr.nnz))
    assert slots[0] != slots[1]
    assert counts[0] == counts[1]
    assert counts[0]["spmm"][0] == work.spmm_cost(300, 2 * 900 + 300, 8)


def test_roofline_reader_share():
    from chipbench import harness
    read = harness.reader("spmm_roofline")
    costs = [(1e9, 819e6)] * 4          # 1 ms each at 819 GB/s
    ctx = {"work": {"spmm": costs}, "peaks": PEAKS, "steps": 2,
           "trace": {"op_seconds": {"paramspmm_v2_k8_r32_d128": 0.4,
                                    "fusion": 1.0}}}
    assert read(ctx) == pytest.approx(100 * 0.008 / 0.4)
    ctx["trace"]["op_seconds"] = {"fusion": 1.0}
    assert read(ctx) is None
    assert np.isfinite(harness.reader("step_mfu")(
        {"work": {"flops": 1e9}, "step_s": 0.5, "peaks": PEAKS}))
