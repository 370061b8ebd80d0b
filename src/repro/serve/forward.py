"""Bucketed GNN forwards — one compilation per (bucket, model, backend).

The hot path is a module-level ``jax.jit`` function whose *traced*
operands are the steering arrays, features, and parameters, and whose
*static* operand is the bucket's ``PackGeom``.  Because every batch in a
bucket produces steering arrays of identical shapes (``pack_subgraph``),
the compiled program is reused for the life of the process — the
closure-style builders in ``core.engine`` (which bake the arrays in as
constants and therefore recompile per graph) must never appear here.

``serve_recompiles_total`` increments *at trace time only* (the Python
body of a jitted function runs once per compilation), making it a true
recompile counter: the soak test asserts it stays flat after one
warm-up pass per bucket.

Layer semantics are literally ``models.gnn.gcn_forward`` /
``gin_forward`` / ``gat_forward`` — the serve path only swaps in a
steering-array-parameterized aggregation closure, so serving cannot
drift from the training forward.  Exactness: with integer-valued
features/weights the GCN/GIN serve output is bit-equal to the
full-pipeline reference (padding slots add exact zeros; integer sums are
order-free); GAT's softmax normalizer is summed in layout order, so the
serve output matches the reference to float tolerance, not bits.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.engine import (_engine, _engine_sddmm, _slot_rows,
                               apply_epilogue, attend_scores, engine_spmm,
                               engine_spmm_fused, make_gat_message_fn)
from repro.core.pcsr import build_pcsr
from repro.kernels import resolve_backend
from repro.models.gnn import gat_forward, gcn_forward, gin_forward
from repro.obs import metrics as _metrics

from .bucket import PackGeom


def _bucket_spmm(steer, geom: PackGeom, backend: str, interpret: bool):
    """``spmm(B)`` + ``.fused(...)`` closures over *traced* steering
    arrays with static bucket geometry — the serving analogue of
    ``ParamSpMMOperator``'s fusion surface."""
    cfg = geom.config

    if backend == "pallas":
        from repro.kernels.paramspmm.ops import _call

        def call(B, scale=None, bias=None, activation="none", residual=None):
            return _call(steer["colidx"], steer["lrow"], steer["trow"],
                         steer["init"], steer["fini"], steer["vals"], B,
                         None, None, scale, bias, residual, cfg=cfg,
                         n_blocks=geom.n_blocks, K=geom.K,
                         n_rows=geom.n_rows, dim=B.shape[1],
                         activation=activation, interpret=interpret)

        def spmm(B):
            return call(B)

        def fused(B, scale=None, bias=None, activation="none",
                  residual=None):
            return call(B, scale, bias, activation, residual)
    else:
        def spmm(B):
            return _engine(steer["colidx"], steer["lrow"], steer["trow"],
                           steer["vals"], B, V=cfg.V, R=cfg.R, K=geom.K,
                           n_blocks=geom.n_blocks, n_rows=geom.n_rows)

        def fused(B, scale=None, bias=None, activation="none",
                  residual=None):
            return apply_epilogue(spmm(B), scale, bias, activation,
                                  residual=residual)

    spmm.fused = fused
    return spmm


def _bucket_gat_msg(steer, geom: PackGeom, backend: str, interpret: bool,
                    slope: float = 0.2):
    """Single-head fused GAT message over traced steering arrays —
    SDDMM → LeakyReLU → edge softmax → SpMM, same two-kernel structure
    as ``make_gat_message_fn`` but shape-stable across requests."""
    cfg = geom.config
    V, R, K, nb = cfg.V, cfg.R, geom.K, geom.n_blocks

    if backend == "pallas":
        from repro.kernels.paramspmm.ops import _call
        from repro.kernels.sddmm.ops import _stats_call

        def msg(Q, K_mat, Vf):
            scale = float(1.0 / np.sqrt(Q.shape[-1]))
            logits, rowmax, rowsum = _stats_call(
                steer["colidx"], steer["lrow"], steer["trow"],
                steer["init"], steer["vals"], Q[None], K_mat[None],
                H=1, n_blocks=nb, R=R, W=cfg.W, V=V, K=K, dblk=cfg.dblk,
                scale=scale, slope=slope, interpret=interpret)
            logits = logits.reshape(geom.num_chunks, V, K)
            return _call(steer["colidx"], steer["lrow"], steer["trow"],
                         steer["init"], steer["fini"], logits, Vf,
                         rowmax, rowsum, cfg=cfg, n_blocks=nb, K=K,
                         n_rows=geom.n_rows, dim=Vf.shape[1],
                         interpret=interpret)
    else:
        def msg(Q, K_mat, Vf):
            mask = steer["vals"] != 0
            rows = _slot_rows(steer["lrow"], steer["trow"], V=V, R=R, K=K)
            scores = _engine_sddmm(steer["colidx"], steer["lrow"],
                                   steer["trow"], steer["vals"], Q, K_mat,
                                   V=V, R=R, K=K)
            alpha = attend_scores(scores, mask, rows, nb * R,
                                  dim_k=Q.shape[1], slope=slope)
            return _engine(steer["colidx"], steer["lrow"], steer["trow"],
                           alpha, Vf, V=V, R=R, K=K, n_blocks=nb,
                           n_rows=geom.n_rows)

    return msg


@functools.partial(jax.jit,
                   static_argnames=("geom", "model", "backend", "interpret"))
def bucket_forward(steer, X, params, *, geom: PackGeom, model: str,
                   backend: str, interpret: bool):
    """Full GNN forward on one bucket-padded batch.

    Traced: ``steer`` (steering dict from ``steering_arrays``), ``X``
    (``(geom.n_rows, f)`` padded features), ``params`` (the model's
    parameter pytree).  Static: the bucket geometry + model/backend —
    the complete jit cache key.  Rows past the real batch are padding
    (zero features, zero edges) and are sliced off by the caller.
    """
    _metrics.counter("serve_recompiles_total").inc(
        model=model, backend=backend,
        bucket=f"r{geom.n_rows}c{geom.num_chunks}")
    if model == "gcn":
        return gcn_forward(params, X, _bucket_spmm(steer, geom, backend,
                                                   interpret))
    if model == "gin":
        return gin_forward(params, X, _bucket_spmm(steer, geom, backend,
                                                   interpret))
    if model == "gat":
        return gat_forward(params, X, _bucket_gat_msg(steer, geom, backend,
                                                      interpret))
    raise ValueError(f"unknown model {model!r}")


def reference_forward(csr, X, params, *, model: str, config,
                      backend: str | None = None,
                      interpret: bool | None = None):
    """The full-pipeline forward on an *unpadded* subgraph — the serving
    exactness oracle.  Builds a fresh PCSR under ``config`` (pass the
    serving pack's config: GAT's softmax is layout-sensitive) and runs
    the same ``models.gnn`` forward through the standard closure
    builders."""
    backend, interpret = resolve_backend(backend, interpret)
    p = build_pcsr(csr.indptr, csr.indices, csr.data, csr.n_rows,
                   csr.n_cols, config)
    X = jnp.asarray(X)
    if model == "gat":
        msg = make_gat_message_fn(p, backend=backend, interpret=interpret)
        return gat_forward(params, X, msg)

    if backend == "pallas":
        from repro.kernels.paramspmm.ops import paramspmm

        def spmm(B):
            return paramspmm(p, B, interpret=interpret)

        def fused(B, scale=None, bias=None, activation="none",
                  residual=None):
            return paramspmm(p, B, scale=scale, bias=bias,
                             residual=residual, activation=activation,
                             interpret=interpret)
    else:
        def spmm(B):
            return engine_spmm(p, B)

        def fused(B, scale=None, bias=None, activation="none",
                  residual=None):
            return engine_spmm_fused(p, B, scale=scale, bias=bias,
                                     residual=residual,
                                     activation=activation)

    spmm.fused = fused
    fwd = {"gcn": gcn_forward, "gin": gin_forward}[model]
    return fwd(params, X, spmm)
