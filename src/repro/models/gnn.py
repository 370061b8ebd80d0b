"""GCN, GIN (paper §6.5) and GAT with pluggable sparse aggregation.

GCN/GIN take a `spmm: (n, d) -> (n, d)` closure over the graph — either a
ParamSpMM operator (decider-configured) or a baseline path — so "embed
ParamSpMM into GNN training" is literally swapping this callable, as the
paper does with its PyTorch extension.  Closures that additionally expose
``.fused(B, scale=, bias=, activation=)`` (ParamSpMM / ParamSpMMOperator
/ DistGraph) get each GCN layer's bias + ReLU handed to the SpMM's fused
epilogue — one kernel per aggregation on the Pallas backend.  GAT
instead takes the fused message closure `msg: (Q, K, Vf) -> (n, d)`
built by ``core.engine.make_gat_message_fn`` (two kernels: SDDMM→softmax
stats, prologue SpMM), mirroring HGL-proto's GSDDMM/GSPMM operator pair.

The distributed operators plug into the same seams with global shapes:
``repro.dist.DistGraph`` is a `(n, d) -> (n, d)` spmm closure (with the
same ``.fused`` epilogue surface) and its ``.gat_message`` a message
closure accepting the same single-head `(n, d)` or multi-head
`(H, n, d)` stacks as the single-device message fn — the models never
see the mesh, the partitioning, the per-shard configs, or whether the
halo gather is overlapped with compute (`apps/gnn.py --partitions N
[--heads H] [--overlap]` wires them in; docs/DISTRIBUTED.md walks
through scaling a GAT to N shards).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _dense_init(key, fan_in, fan_out):
    scale = jnp.sqrt(2.0 / fan_in)
    return jax.random.normal(key, (fan_in, fan_out), jnp.float32) * scale


# -------------------------------------------------------------------- GCN
def init_gcn(key, layer_dims):
    """layer_dims e.g. [16, 64, 64, 64, 64, 16] → 5 layers (paper setup)."""
    params = []
    for i in range(len(layer_dims) - 1):
        key, k1 = jax.random.split(key)
        params.append({
            "w": _dense_init(k1, layer_dims[i], layer_dims[i + 1]),
            "b": jnp.zeros(layer_dims[i + 1], jnp.float32),
        })
    return params


def gcn_forward(params, X, spmm):
    """One GCN layer is ``relu(Â·H·W + b)``.  When the aggregation closure
    exposes the epilogue-fusion surface (``spmm.fused`` — ParamSpMM /
    ParamSpMMOperator / DistGraph), the layer reassociates to
    ``Â·(H·W)`` and hands bias + activation to the SpMM epilogue: the
    whole aggregation step is ONE kernel on the Pallas backend (the
    bias/ReLU passes ride the VMEM-resident output block) instead of
    kernel + 2–3 XLA elementwise passes over the (n, d) output."""
    fused = getattr(spmm, "fused", None)
    h = X
    for i, layer in enumerate(params):
        last = i == len(params) - 1
        w = layer["w"]
        # fuse only when transform-then-aggregate doesn't widen the SpMM:
        # the epilogue needs the SpMM last, i.e. Â·(H·W) — a win (and the
        # one-kernel layer) for d_out ≤ d_in, a wider gather otherwise
        if fused is not None and w.shape[1] <= w.shape[0]:
            h = fused(h @ w, bias=layer["b"],
                      activation="none" if last else "relu")
        else:
            h = spmm(h) @ w + layer["b"]               # Â·H·W
            if not last:
                h = jax.nn.relu(h)
    return h


# -------------------------------------------------------------------- GIN
def init_gin(key, layer_dims, mlp_hidden_mult: int = 1):
    params = []
    for i in range(len(layer_dims) - 1):
        key, k1, k2 = jax.random.split(key, 3)
        hid = layer_dims[i + 1] * mlp_hidden_mult
        params.append({
            "eps": jnp.zeros((), jnp.float32),
            "w1": _dense_init(k1, layer_dims[i], hid),
            "b1": jnp.zeros(hid, jnp.float32),
            "w2": _dense_init(k2, hid, layer_dims[i + 1]),
            "b2": jnp.zeros(layer_dims[i + 1], jnp.float32),
        })
    return params


def gin_forward(params, X, spmm):
    """GIN aggregation is ``(1+ε)h + A·h``.  When the aggregation closure
    exposes a residual-capable fused epilogue (``spmm.fused(...,
    residual=)`` — ParamSpMM / ParamSpMMOperator), the ``(1+ε)h`` term is
    handed to the SpMM epilogue as the dense residual addend: the whole
    aggregation is ONE kernel on the Pallas backend — the addend rides
    the VMEM-resident output block — instead of kernel + an XLA add pass
    over the (n, d) output."""
    import inspect
    fused = getattr(spmm, "fused", None)
    if fused is not None:
        try:
            if "residual" not in inspect.signature(fused).parameters:
                fused = None                # e.g. DistGraph: no residual yet
        except (TypeError, ValueError):
            fused = None
    h = X
    for i, layer in enumerate(params):
        if fused is not None:
            agg = fused(h, residual=(1.0 + layer["eps"]) * h)
        else:
            agg = (1.0 + layer["eps"]) * h + spmm(h)   # (1+ε)h + A·h
        z = jax.nn.relu(agg @ layer["w1"] + layer["b1"])
        h = z @ layer["w2"] + layer["b2"]
        if i < len(params) - 1:
            h = jax.nn.relu(h)
    return h


# -------------------------------------------------------------------- GAT
def init_gat(key, layer_dims, att_dim: int | None = None, heads: int = 1):
    """Dot-product attention GAT: per layer Wq/Wk project into the
    attention space (att_dim per head, default = per-head output dim), Wv
    transforms the message features.

    Multi-head (``heads > 1``) follows the standard GAT scheme: hidden
    layers concatenate the per-head outputs (layer dim must divide by
    ``heads``), the final layer averages full-width heads.
    """
    params = []
    L = len(layer_dims) - 1
    for i in range(L):
        key, kq, kk, kv = jax.random.split(key, 4)
        out = layer_dims[i + 1]
        concat = heads > 1 and i < L - 1
        if concat and out % heads:
            raise ValueError(f"layer dim {out} not divisible by {heads} heads")
        dv = out // heads if concat else out
        da = att_dim or dv
        params.append({
            "wq": _dense_init(kq, layer_dims[i], heads * da),
            "wk": _dense_init(kk, layer_dims[i], heads * da),
            "wv": _dense_init(kv, layer_dims[i], heads * dv),
            "b": jnp.zeros(out, jnp.float32),
        })
    return params


def gat_forward(params, X, gat_msg, heads: int = 1):
    """h'_i = Σ_j α_ij · (h_j·Wv), α = softmax_j(LeakyReLU(q_i·k_j/√d)).

    With ``heads > 1`` the projections are split into (H, n, d_head)
    stacks and handed to ``gat_msg`` as one batch — the message fn (see
    ``core.engine.make_gat_message_fn``) runs every head through a single
    kernel call per kernel, so the layer compiles once however many heads.
    """
    h = X
    L = len(params)
    for i, layer in enumerate(params):
        q, k, v = h @ layer["wq"], h @ layer["wk"], h @ layer["wv"]
        if heads == 1:
            h = gat_msg(q, k, v) + layer["b"]
        else:
            n = h.shape[0]
            split = lambda m: m.reshape(n, heads, -1).transpose(1, 0, 2)
            msg = gat_msg(split(q), split(k), split(v))    # (H, n, dv)
            if i < L - 1:                                  # concat heads
                h = msg.transpose(1, 0, 2).reshape(n, -1) + layer["b"]
            else:                                          # average heads
                h = msg.mean(axis=0) + layer["b"]
        if i < L - 1:
            h = jax.nn.relu(h)
    return h


# ------------------------------------------------------------------ loss
def node_ce_loss(logits, labels, mask):
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    return -(ll * mask).sum() / jnp.maximum(mask.sum(), 1.0)


def accuracy(logits, labels, mask):
    pred = logits.argmax(-1)
    return ((pred == labels) * mask).sum() / jnp.maximum(mask.sum(), 1.0)
