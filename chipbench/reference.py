"""Plain float32 references, independent of the program under test.

Everything here is written from the model's equations in plain
``jax.numpy``: a symmetric GCN-normalised adjacency built from the edge
list, aggregation by gather + ``segment_sum`` (no PCSR), the GCN and
dot-product GAT forwards, the masked cross-entropy and AdamW.  Nothing is imported from the
program and nothing it made (weights, packs, normalised values) is used.

``precision`` selects how dense matrix products run: ``"highest"`` is
float32 (six bf16 passes on a TPU), ``"high"`` is three bf16 passes,
written out here so that the lower-precision control computes the same
numbers on every backend.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np


# ------------------------------------------------------------ adjacency
@dataclass
class Adjacency:
    """Row-sorted COO of D^-1/2 (A + I) D^-1/2."""
    rows: np.ndarray
    cols: np.ndarray
    vals: np.ndarray
    n: int


def gcn_adjacency(u: np.ndarray, v: np.ndarray, n: int) -> Adjacency:
    """Symmetrise the undirected edges ``(u, v)``, add one self loop per
    node and normalise by the degrees (self loop included)."""
    r = np.concatenate([u, v, np.arange(n)]).astype(np.int64)
    c = np.concatenate([v, u, np.arange(n)]).astype(np.int64)
    key = np.unique(r * n + c)
    r, c = key // n, key % n
    deg = np.bincount(r, minlength=n).astype(np.float32)
    dinv = (1.0 / np.sqrt(deg)).astype(np.float32)
    vals = (np.ones(r.size, np.float32) * dinv[r] * dinv[c]).astype(np.float32)
    return Adjacency(r, c, vals, n)


# ----------------------------------------------------------- dense math
def dot(a, b, precision: str):
    """``a @ b`` in float32 (``highest``) or by three bf16 passes
    (``high``): hi·hi + hi·lo + lo·hi, accumulated in float32."""
    if precision == "highest":
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)
    if precision != "high":
        raise ValueError(f"unknown precision {precision!r}")
    bf = jnp.bfloat16
    ah = a.astype(bf)
    al = (a - ah.astype(a.dtype)).astype(bf)
    bh = b.astype(bf)
    bl = (b - bh.astype(b.dtype)).astype(bf)
    mm = functools.partial(jnp.matmul, preferred_element_type=jnp.float32)
    return mm(ah, bh) + mm(ah, bl) + mm(al, bh)


def segment_spmm(adj: Adjacency):
    """``A·B`` by gather + segment-sum over the edges of ``adj``."""
    rows = jnp.asarray(adj.rows, jnp.int32)
    cols = jnp.asarray(adj.cols, jnp.int32)
    vals = jnp.asarray(adj.vals, jnp.float32)
    return lambda B: jax.ops.segment_sum(vals[:, None] * B[cols], rows,
                                         num_segments=adj.n)


def segment_gat_message(adj: Adjacency, slope: float = 0.2,
                        chunk: int = 1 << 16):
    """Per destination row softmax_j(LeakyReLU(q_i·k_j/√d)), then
    Σ_j α_ij·v_j over the edge pattern of ``adj``; operands are
    ``(H, n, d)`` stacks.  The gathered ``(E, d)`` rows are made
    ``chunk`` edges at a time under ``jax.checkpoint``, so the backward
    pass recomputes them instead of keeping them; padding edges score
    −inf and weigh 0."""
    n, nnz = adj.n, adj.rows.size
    n_chunks = -(-nnz // chunk)
    pad = n_chunks * chunk - nnz
    rows = jnp.asarray(np.pad(adj.rows, (0, pad)), jnp.int32)
    cols = jnp.asarray(np.pad(adj.cols, (0, pad)), jnp.int32)
    real = jnp.arange(n_chunks * chunk) < nnz
    by_chunk = lambda x: x.reshape(n_chunks, chunk)

    def one(q, k, v):
        @jax.checkpoint
        def scores(rc):
            r, c = rc
            return jnp.sum(q[r] * k[c], axis=1)

        @jax.checkpoint
        def messages(rcw):
            r, c, w = rcw
            return jax.ops.segment_sum(w[:, None] * v[c], r, num_segments=n)

        s = jax.lax.map(scores, (by_chunk(rows), by_chunk(cols))).reshape(-1)
        s = s / jnp.sqrt(jnp.asarray(q.shape[1], q.dtype))
        s = jnp.where(real, jax.nn.leaky_relu(s, negative_slope=slope),
                      -jnp.inf)
        m = jax.ops.segment_max(s, rows, num_segments=n)
        e = jnp.exp(s - m[rows])
        den = jax.ops.segment_sum(e, rows, num_segments=n)
        w = e / den[rows]
        return jax.lax.map(messages, (by_chunk(rows), by_chunk(cols),
                                      by_chunk(w))).sum(0)

    return lambda Q, K, V: jnp.stack([one(*qkv) for qkv in zip(Q, K, V)])


# --------------------------------------------------------------- models
def gcn_forward(params, X, agg, precision: str):
    """relu(Â·H·W + b) per layer, no activation after the last."""
    h = X
    for i, layer in enumerate(params):
        h = dot(agg(h), layer["w"], precision) + layer["b"]
        if i < len(params) - 1:
            h = jax.nn.relu(h)
    return h


def gat_forward(params, X, msg, heads: int, precision: str):
    """Dot-product GAT: q, k, v projections split into heads, attention
    message per head; hidden layers concatenate heads, the last averages
    them; ReLU between layers."""
    h = X
    n = X.shape[0]
    for i, layer in enumerate(params):
        split = lambda m: m.reshape(n, heads, -1).transpose(1, 0, 2)
        q, k, v = (split(dot(h, layer[w], precision))
                   for w in ("wq", "wk", "wv"))
        out = msg(q, k, v)
        if i < len(params) - 1:
            h = jax.nn.relu(out.transpose(1, 0, 2).reshape(n, -1)
                            + layer["b"])
        else:
            h = out.mean(axis=0) + layer["b"]
    return h


def node_ce_loss(logits, labels, mask):
    """Mean cross-entropy over the nodes where ``mask`` is 1."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    return -(ll * mask).sum() / jnp.maximum(mask.sum(), 1.0)


def adamw(params, grads, state, opt: dict):
    """One AdamW step (bias-corrected moments, decoupled weight decay)."""
    step = state["step"] + 1
    b1, b2 = opt["b1"], opt["b2"]
    c1 = 1.0 - b1 ** step.astype(jnp.float32)
    c2 = 1.0 - b2 ** step.astype(jnp.float32)
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, state["m"], grads)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, state["v"],
                     grads)
    new = jax.tree.map(
        lambda p, m, v: p - opt["lr"] * ((m / c1) / (jnp.sqrt(v / c2)
                                                     + opt["eps"])
                                         + opt["weight_decay"] * p),
        params, m, v)
    return new, {"m": m, "v": v, "step": step}


def adamw_init(params):
    zeros = lambda p: jnp.zeros_like(p)
    return {"m": jax.tree.map(zeros, params),
            "v": jax.tree.map(zeros, params),
            "step": jnp.zeros((), jnp.int32)}


def forward_fn(model: dict, adj: Adjacency, precision: str):
    """``f(params, X)`` of the configuration's model over ``adj``."""
    if model["kind"] == "gcn":
        agg = segment_spmm(adj)
        return lambda p, X: gcn_forward(p, X, agg, precision)
    if model["kind"] == "dpgat":
        msg = segment_gat_message(adj)
        return lambda p, X: gat_forward(p, X, msg, model["heads"], precision)
    raise ValueError(f"unknown model kind {model['kind']!r}")


def train_readings(model: dict, opt: dict, adj: Adjacency, params, X, y,
                   mask, precision: str = "highest", steps: int = 3):
    """The reference's readings over ``steps`` AdamW steps from
    ``params``: each step's loss, each leaf's first-gradient norm, and
    each leaf's parameter change after the first and the last step (host
    floats)."""
    fwd = forward_fn(model, adj, precision)

    @jax.jit
    def reference_step(p, s, X, y, mask):
        loss, g = jax.value_and_grad(
            lambda p: node_ce_loss(fwd(p, X), y, mask))(p)
        p, s = adamw(p, g, s, opt)
        return loss, g, p, s

    state = adamw_init(params)
    p, losses, grad_norms = params, [], None
    for i in range(steps):
        loss, g, p, state = reference_step(p, state, X, y, mask)
        losses.append(float(loss))
        if i == 0:
            grad_norms = leaf_norms(g)
            delta1 = change_norms(p, params)
    return {"losses": losses, "grad_norms": grad_norms,
            "delta1_norms": delta1, "delta_norms": change_norms(p, params)}


@jax.jit
def _norms(tree):
    return [jnp.sqrt(jnp.sum(jnp.square(x))) for x in jax.tree.leaves(tree)]


def leaf_norms(tree) -> list:
    """Euclidean norm of every leaf, in ``jax.tree.leaves`` order."""
    return [float(x) for x in _norms(tree)]


def change_norms(new, old) -> list:
    """Norm of every leaf's change from ``old`` to ``new``."""
    return leaf_norms(jax.tree.map(lambda a, b: a - b, new, old))
