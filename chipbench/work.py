"""Work a training step requires, counted from the configuration's shapes.

Every count is of what the model's mathematics needs, not of what the
program does: each aggregation at the narrower of its layer's two widths,
a backward product only where a gradient flows, no recomputation, and
compulsory bytes only (the sparse operand's indices and values once, each
dense operand once, the output once, 4 bytes per float32 or int32).  So
the counts are the same whatever kernel configuration, lane padding,
association or recompute the program picks, and a share computed from
them reads over 100% only where work was skipped.

``n`` is the node count and ``nnz`` the nonzeros of the GCN-normalised
adjacency (edges both ways plus one self loop per node).
"""
from __future__ import annotations

F32 = 4


def model_dims(model: dict) -> list:
    """Layer widths: input, hidden ones, classes."""
    return ([model["in_features"]] + [model["hidden"]] * (model["layers"] - 1)
            + [model["classes"]])


def spmm_cost(n: int, nnz: int, d: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one ``A·B`` with ``B`` of width ``d``: a multiply
    and an add per nonzero and column; CSR indices, row pointers and
    values, ``B`` and the output once."""
    return 2.0 * nnz * d, F32 * (2 * nnz + n + 1) + 2.0 * F32 * n * d


def sddmm_cost(n: int, nnz: int, d: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one sampled product ``s_ij = x_i·y_j`` over the
    pattern: a multiply and an add per nonzero and column; indices and
    row pointers, both dense operands and one score per nonzero."""
    return 2.0 * nnz * d, F32 * (2 * nnz + n + 1) + 2.0 * F32 * n * d


def dense_flops(n: int, d_in: int, d_out: int, grad_input: bool) -> float:
    """``X·W`` forward, ``Xᵀ·dY`` for the weight gradient, and ``dY·Wᵀ``
    where the input needs a gradient."""
    return 2.0 * n * d_in * d_out * (3 if grad_input else 2)


def step_work(model: dict, n: int, nnz: int) -> dict:
    """FLOPs and bytes of one training step: ``spmm`` and ``sddmm`` list
    (flops, bytes) per product, ``dense`` is the dense products' FLOPs
    and ``flops`` the whole step's model FLOPs."""
    dims = model_dims(model)
    spmm, sddmm = [], []
    dense = 0.0

    def add(acc, cost, times=1):
        acc.extend([cost] * times)

    for i, (d_in, d_out) in enumerate(zip(dims[:-1], dims[1:])):
        first = i == 0
        if model["kind"] == "gcn":
            d = min(d_in, d_out)
            # the first layer aggregates the input features, which need
            # no gradient, where it aggregates before transforming
            agg_needs_grad = not (first and d_in < d_out)
            add(spmm, spmm_cost(n, nnz, d), 2 if agg_needs_grad else 1)
            dense += dense_flops(n, d_in, d_out, grad_input=not first)
        elif model["kind"] == "dpgat":
            H = model["heads"]
            last = i == len(dims) - 2
            dv = d_out if last else d_out // H
            for _ in range(H):
                # forward: scores, then α·V; backward: dα = dOut·Vᵀ over
                # the pattern, then dV = αᵀ·dOut, dQ = dS·K, dK = dSᵀ·Q
                add(sddmm, sddmm_cost(n, nnz, dv), 2)
                add(spmm, spmm_cost(n, nnz, dv), 4)
            dense += 3 * dense_flops(n, d_in, H * dv, grad_input=not first)
        else:
            raise ValueError(f"unknown model kind {model['kind']!r}")
    return {"spmm": spmm, "sddmm": sddmm, "dense": dense,
            "flops": dense + sum(f for f, _ in spmm + sddmm)}


def least_seconds(costs: list, peaks: dict) -> float:
    """The roofline of a list of (flops, bytes) products: for each, the
    larger of compute time at the FLOP peak and transfer time at the HBM
    bandwidth, summed."""
    return sum(max(f / peaks["flops_bf16"], b / peaks["hbm_bytes_per_s"])
               for f, b in costs)
