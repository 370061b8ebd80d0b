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

from repro.core.engine import (gat_forward as gat_message, make_gat_message_fn,
                               steered_spmm)
from repro.core.pcsr import build_pcsr
from repro.kernels import resolve_backend
from repro.models.gnn import gat_forward, gcn_forward, gin_forward
from repro.obs import metrics as _metrics

from .bucket import PackGeom


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
    if model == "gat":
        def msg(Q, K, Vf):     # a bucket's covered steering is uncovered
            return gat_message(steer, steer, Q, K, Vf, geom=geom,
                               backend=backend, interpret=interpret)[0]
        return gat_forward(params, X, msg)
    fwd = {"gcn": gcn_forward, "gin": gin_forward}.get(model)
    if fwd is None:
        raise ValueError(f"unknown model {model!r}")
    return fwd(params, X, steered_spmm(steer, geom, backend=backend,
                                       interpret=interpret))


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

    return {"gcn": gcn_forward, "gin": gin_forward}[model](
        params, X, steered_spmm(p.steering(covered=True), p,
                                backend=backend, interpret=interpret))
