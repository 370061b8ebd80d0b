"""The operators: SpMM, epilogue-fused SpMM and the GAT attention message,
over PCSR steering arrays, on either backend.

The pure-JAX engine (paper Alg. 2) walks the same PCSR traversal as the
Pallas kernel, as gather + scatter-add, so it jit-compiles on any backend
and is differentiable; the kernels in ``repro.kernels`` are the TPU
artifact.  Both are validated against ``kernels/*/ref.py``.

Layering: ``core.pcsr`` packs the format; ``kernels/*/ops.py`` hold each
kernel's one dispatch; this module holds the operators, each written once
over steering arrays that may be host constants (one device), per-shard
traced values (``repro.dist``) or traced bucket arrays (``repro.serve``):

* ``steered_spmm`` / ``steered_sddmm`` — the one place an operator
  chooses its backend;
* ``gat_forward`` / ``gat_backward`` — the GAT attention pipeline: the
  two-kernel forward and its flash-style all-kernel backward;
* ``make_spmm_fn``, ``make_fused_spmm_fn``, ``make_gat_message_fn`` —
  the differentiable closures over one PCSR (the backward SpMM
  ``dB = Aᵀ·dC`` runs a second PCSR built for ``Aᵀ``, as the paper's
  PyTorch extension does).

See docs/OPERATORS.md for the exact pipelines.
"""
from __future__ import annotations

import functools
from time import time_ns
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import resolve_backend
from repro.obs import metrics as _obs_metrics

from .pcsr import PCSR, SpMMConfig, build_pcsr
from .sparse import CSRMatrix


@functools.partial(jax.jit, static_argnames=("V", "R", "K", "n_blocks", "n_rows"))
def _engine(colidx, lrow, trow, vals, B, *, V, R, K, n_blocks, n_rows):
    """Scatter-add evaluation of the packed PCSR chunks."""
    ck = colidx.shape[0]
    gathered = jnp.take(B, colidx, axis=0)                    # (C·K, dim)
    base = jnp.repeat(trow, K).astype(jnp.int32) * R + lrow * V
    valsf = jnp.swapaxes(vals, 1, 2).reshape(ck, V).astype(B.dtype)
    out = jnp.zeros((n_blocks * R, B.shape[1]), B.dtype)
    for v in range(V):                                        # V ≤ 2, unrolled
        out = out.at[base + v].add(valsf[:, v][:, None] * gathered)
    return out[:n_rows]


def engine_spmm(pcsr: PCSR, B):
    """C = A·B on the jit'd JAX engine."""
    arrs = pcsr.to_jax()
    cfg = pcsr.config
    return _engine(arrs["colidx"], arrs["lrow"], arrs["trow"], arrs["vals"],
                   jnp.asarray(B), V=cfg.V, R=cfg.R, K=pcsr.K,
                   n_blocks=pcsr.n_blocks, n_rows=pcsr.n_rows)


@functools.partial(jax.jit, static_argnames=("V", "R", "K"))
def _engine_sddmm(colidx, lrow, trow, vals, Q, K_mat, *, V, R, K):
    """Gather/dot evaluation of per-slot SDDMM scores (C, V, K)."""
    ck = colidx.shape[0]
    C = ck // K
    gathered = jnp.take(K_mat, colidx, axis=0)                # (C·K, d)
    base = jnp.repeat(trow, K).astype(jnp.int32) * R + lrow * V
    scores = []
    for v in range(V):                                        # V ≤ 2, unrolled
        # rows past n_rows (block padding) read as zero → score 0
        qrow = jnp.take(Q, base + v, axis=0, mode="fill", fill_value=0)
        scores.append(jnp.sum(qrow * gathered, axis=1))
    e = jnp.stack(scores, axis=1)                             # (C·K, V)
    e = jnp.swapaxes(e.reshape(C, K, V), 1, 2)                # (C, V, K)
    return jnp.where(vals != 0, e, 0.0)


def engine_sddmm(pcsr: PCSR, Q, K_mat):
    """E = (A≠0) ⊙ (Q·Kᵀ) in PCSR slot layout, on the jit'd JAX engine."""
    arrs = pcsr.to_jax()
    cfg = pcsr.config
    return _engine_sddmm(arrs["colidx"], arrs["lrow"], arrs["trow"],
                         arrs["vals"], jnp.asarray(Q), jnp.asarray(K_mat),
                         V=cfg.V, R=cfg.R, K=pcsr.K)


def _slot_rows(lrow, trow, *, V, R, K):
    """Destination row of every slot, in (C, V, K) layout."""
    C = trow.shape[0]
    base = trow[:, None, None].astype(jnp.int32) * R \
        + lrow.reshape(C, 1, K) * V
    return base + jnp.arange(V, dtype=jnp.int32)[None, :, None]


def edge_softmax(scores, mask, rows, n_segments: int):
    """Numerically-stable softmax over each destination row's edge set.

    scores/mask/rows all (C, V, K); padding slots (mask False) get weight 0
    and never contribute to their row's max or normalizer.
    """
    flat_r = rows.reshape(-1)
    neg = jnp.where(mask, scores, -jnp.inf).reshape(-1)
    rowmax = jax.ops.segment_max(neg, flat_r, num_segments=n_segments)
    rowmax = jnp.where(jnp.isfinite(rowmax), rowmax, 0.0)     # empty rows
    ex = jnp.exp(neg - rowmax[flat_r])
    ex = jnp.where(mask.reshape(-1), ex, 0.0)
    denom = jax.ops.segment_sum(ex, flat_r, num_segments=n_segments)
    alpha = ex / jnp.maximum(denom[flat_r], 1e-30)
    return alpha.reshape(scores.shape)


def attend_scores(scores, mask, rows, n_segments: int, *,
                  dim_k: int, slope: float = 0.2):
    """The GAT attention step of the engine's ``gat_forward``: scale raw
    SDDMM scores by 1/√d_k, LeakyReLU(slope), softmax over each
    destination row's edge set."""
    scaled = scores / jnp.sqrt(jnp.asarray(dim_k, scores.dtype))
    scaled = jax.nn.leaky_relu(scaled, negative_slope=slope)
    return edge_softmax(scaled, mask, rows, n_segments)


def _observed_build(op: str):
    """Record each call's wall time in ``spmm_build_seconds{op}``: the
    host set-up of an aggregation operator, beside ``ParamSpMM``'s."""
    def wrap(build):
        @functools.wraps(build)
        def observed(*args, **kwargs):
            t0 = time_ns()
            out = build(*args, **kwargs)
            _obs_metrics.histogram("spmm_build_seconds").observe(
                (time_ns() - t0) * 1e-9, op=op)
            return out
        return observed
    return wrap


def _head0(st, C: int, K: int):
    """The first ``C`` chunks of a steering dict: the uncovered arrays of
    head 0, whose head offsets are zero — coverage chunks and the other
    heads' copies pack after them.  No slice where there is nothing to
    cut."""
    if st["trow"].shape[0] == C:
        return st
    return {"colidx": st["colidx"][:C * K], "lrow": st["lrow"][:C * K],
            "trow": st["trow"][:C], "vals": st["vals"][:C]}


def steered_spmm(st, geom, *, backend: str, interpret: bool | None = None):
    """The SpMM over steering arrays, on either backend:
    ``spmm(B, *, vals=None, stats=None, scale=None, bias=None,
    activation="none", residual=None)`` computes
    ``act(scale ⊙ (A·B) + bias + residual)`` (``spmm.fused`` is the same
    function, the fusion surface ``models.gnn`` looks for).

    ``st`` holds covered steering arrays (``PCSR.steering(H,
    covered=True)``'s keys and layout), as host constants or traced
    values; ``geom`` is anything with the PCSR's static geometry
    (``config``, ``n_rows``, ``n_blocks``, ``num_chunks``, ``K``): a
    ``PCSR`` or a serving bucket's ``PackGeom``.  (H, n, d) operands
    with (H, C, V, K) ``vals`` run every head.  Pallas goes through the
    kernel's one dispatch (``kernels.paramspmm.ops.spmm_steered``); the
    engine traverses head 0's uncovered arrays, vmapped over heads, and
    applies the epilogue in plain JAX.  This is the one place an SpMM
    operator chooses its backend."""
    cfg = geom.config
    if backend == "pallas":
        from repro.kernels.paramspmm.ops import spmm_steered

        def spmm(B, *, vals=None, stats=None, scale=None, bias=None,
                 activation: str = "none", residual=None):
            return spmm_steered(st, B, vals, cfg=cfg, n_blocks=geom.n_blocks,
                                K=geom.K, n_rows=geom.n_rows, stats=stats,
                                scale=scale, bias=bias, residual=residual,
                                activation=activation, interpret=interpret)
    else:
        h0 = _head0(st, geom.num_chunks, geom.K)

        def one(B, vals):
            return _engine(h0["colidx"], h0["lrow"], h0["trow"], vals, B,
                           V=cfg.V, R=cfg.R, K=geom.K,
                           n_blocks=geom.n_blocks, n_rows=geom.n_rows)

        def spmm(B, *, vals=None, stats=None, scale=None, bias=None,
                 activation: str = "none", residual=None):
            if stats is not None:
                raise ValueError("the softmax prologue is a kernel form "
                                 "(backend='pallas')")
            if jnp.ndim(B) == 3:
                out = jax.vmap(one, in_axes=(0, None if vals is None else 0))(
                    B, h0["vals"] if vals is None else vals)
            else:
                out = one(B, h0["vals"] if vals is None else vals)
            return apply_epilogue(out, scale, bias, activation,
                                  residual=residual)
    spmm.fused = spmm
    return spmm


def steered_sddmm(st, geom, *, backend: str, interpret: bool | None = None):
    """The SDDMM over *uncovered* steering arrays (``PCSR.steering(H)``'s
    layout, constant or traced), on either backend:
    ``sddmm(Q, K_mat)`` returns E = (A≠0) ⊙ (Q·Kᵀ) in slot layout, (C, V,
    K) for (n, d) operands and (H, C, V, K) for (H, n, d) stacks.  Pallas
    goes through the kernel's one dispatch
    (``kernels.sddmm.ops.sddmm_steered``); the engine gathers over head
    0's arrays, vmapped over heads.  ``geom`` as in ``steered_spmm``."""
    cfg = geom.config
    if backend == "pallas":
        from repro.kernels.sddmm.ops import sddmm_steered

        def sddmm(Q, K_mat):
            if jnp.ndim(Q) == 2:
                return sddmm(Q[None], K_mat[None])[0]
            return sddmm_steered(st, Q, K_mat, cfg=cfg,
                                 n_blocks=geom.n_blocks, K=geom.K,
                                 interpret=interpret)
        return sddmm
    h0 = _head0(st, geom.num_chunks, geom.K)

    def sddmm(Q, K_mat):
        if jnp.ndim(Q) == 3:
            return jax.vmap(sddmm)(Q, K_mat)
        return _engine_sddmm(h0["colidx"], h0["lrow"], h0["trow"],
                             h0["vals"], Q, K_mat, V=cfg.V, R=cfg.R,
                             K=geom.K)
    return sddmm


def _heads(x) -> int:
    """Head count of an (n, d) operand (1) or an (H, n, d) stack."""
    return x.shape[0] if jnp.ndim(x) == 3 else 1


def _per_head(f, x, *args):
    """``f`` on one head's slots (C, V, K), or vmapped over a stack's."""
    return jax.vmap(f)(x, *args) if x.ndim == 4 else f(x, *args)


def gat_forward(st, sd, Q, K_mat, Vf, *, geom, backend: str,
                interpret: bool | None = None, slope: float = 0.2):
    """The GAT attention message, forward: SDDMM → scale 1/√d_k →
    LeakyReLU(slope) → softmax over each destination row's edges → SpMM.
    Returns ``(out, residuals)``: out (n_rows, d) for (n, d) operands,
    (H, n_rows, d) for (H, n, d) stacks.

    ``st`` and ``sd`` hold the covered and the uncovered (head-tiled)
    steering arrays — the SpMM's and the SDDMM's,
    ``PCSR.steering(H, covered=True)`` and ``PCSR.steering(H)``'s
    layouts, constant or traced — and ``geom`` the static geometry, as in
    ``steered_spmm``.  On Pallas this is the **two-kernel forward**: the
    stats SDDMM (row max and normaliser accumulated while the score block
    is VMEM resident) hands (logits, rowmax, rowsum) to the SpMM's
    softmax *prologue*, which rebuilds α in-register — α never reaches
    HBM, and the three are the residuals ``gat_backward`` takes.  The
    engine computes α in plain JAX and returns no residuals (it
    differentiates natively)."""
    cfg = geom.config
    spmm = steered_spmm(st, geom, backend=backend, interpret=interpret)
    if backend == "pallas":
        from repro.kernels.sddmm.ops import sddmm_softmax_steered
        single = jnp.ndim(Q) == 2
        logits, rowmax, rowsum = sddmm_softmax_steered(
            sd, Q[None] if single else Q, K_mat[None] if single else K_mat,
            cfg=cfg, n_blocks=geom.n_blocks, K=geom.K, slope=slope,
            interpret=interpret)
        if single:
            logits = logits[0]
        return (spmm(Vf, vals=logits, stats=(rowmax, rowsum)),
                (logits, rowmax, rowsum))
    h0 = _head0(sd, geom.num_chunks, geom.K)
    rows = _slot_rows(h0["lrow"], h0["trow"], V=cfg.V, R=cfg.R, K=geom.K)
    mask = h0["vals"] != 0
    scores = steered_sddmm(sd, geom, backend=backend)(Q, K_mat)
    alpha = _per_head(lambda s: attend_scores(
        s, mask, rows, geom.n_blocks * cfg.R, dim_k=Q.shape[-1],
        slope=slope), scores)
    return spmm(Vf, vals=alpha), None


def gat_backward(st, sd, st_t, f_idx, t_idx, res, dOut, *, geom, geom_t,
                 interpret: bool | None = None, slope: float = 0.2):
    """``(dQ, dK, dVf)`` of the Pallas ``gat_forward``, flash-style: the
    residuals ``res = (Q, K_mat, Vf, logits, rowmax, rowsum)`` hold no α,
    which is recomputed from the stats; every heavy op is a kernel over
    the forward or the transpose steering:

        α   = exp(logits − rowmax)/rowsum       (recompute, no residual)
        dα  = SDDMM(A,  dOut, Vf)              (dα_ij = dOut_i·Vf_j)
        dx  = α ⊙ (dα − Σ_row α·dα)            (softmax vjp, per-slot)
        de  = dx · scale · LeakyReLU'(x)        (activation chain)
        dQ  = SpMM(A,  de, K)                  (row-gather of keys)
        dK  = SpMM(Aᵀ, deᵀ, Q)                 (transpose-PCSR SpMM)
        dVf = SpMM(Aᵀ, αᵀ, dOut)               (transpose-PCSR SpMM)

    ``st``/``sd`` are A's covered and uncovered steering (as in
    ``gat_forward``), ``st_t`` Aᵀ's covered one, with geometries
    ``geom``/``geom_t``; slot tensors move onto Aᵀ's layout through the
    transfer maps ``f_idx``/``t_idx`` (``core.pcsr.slot_transfer_map``;
    entries past Aᵀ's slots drop, so the maps may be padded).  dK and
    dVf span Aᵀ's rows (A's columns)."""
    from repro.kernels.sddmm.ops import normalize_from_stats, unpack_stats
    Q, K_mat, Vf, logits, rowmax, rowsum = res
    cfg = geom.config
    V, R, K, C, nb = cfg.V, cfg.R, geom.K, geom.num_chunks, geom.n_blocks
    # head 0's slot→row map (its head offsets are zero); host steering
    # makes these constants of the program
    h0 = _head0(sd, C, K)
    with jax.ensure_compile_time_eval():
        lrow, trow = jnp.asarray(h0["lrow"]), jnp.asarray(h0["trow"])
        flat_rows = _slot_rows(lrow, trow, V=V, R=R, K=K).reshape(-1)

    def alpha_1h(lg, rm, rs):
        """α recomputed from the stats residuals (one head) — the one
        normalize implementation, shared with ``kernels.sddmm.ops``, so
        the masked-slot/empty-row guard convention cannot drift."""
        return normalize_from_stats(lg, rm, rs, lrow, trow, R=R, V=V, K=K)

    def row_sum(x):
        """Per-slot broadcast of Σ over each destination row's slots."""
        s = jax.ops.segment_sum(x.reshape(-1), flat_rows, num_segments=nb * R)
        return s[flat_rows].reshape(x.shape)

    rm = unpack_stats(rowmax, R)           # tile-aligned → dense (·, R)
    rs = unpack_stats(rowsum, R)
    if logits.ndim == 4:                   # (H, C, V, K): stats per head
        H = logits.shape[0]
        rm, rs = rm.reshape(H, -1, R), rs.reshape(H, -1, R)
    alpha = _per_head(alpha_1h, logits, rm, rs)
    scale = 1.0 / jnp.sqrt(jnp.asarray(Q.shape[-1], dOut.dtype))
    dalpha = steered_sddmm(sd, geom, backend="pallas",
                           interpret=interpret)(dOut, Vf)
    dx = alpha * (dalpha - _per_head(row_sum, alpha * dalpha))  # softmax vjp
    # LeakyReLU' from the saved logits: LeakyReLU preserves sign, so
    # sign(logits) = sign(pre-activation); masked slots (logit −inf)
    # have dx = 0, so the slope branch they fall into is inert.
    de = dx * scale * jnp.where(logits >= 0, 1.0, slope)
    n_tslots = geom_t.num_chunks * V * geom_t.K

    def to_transpose(x):
        """Re-lay a (..., C, V, K) slot tensor onto Aᵀ's slots."""
        lead = x.shape[:-3]
        tf = jnp.zeros(lead + (n_tslots,), x.dtype)
        tf = tf.at[..., t_idx].set(x.reshape(lead + (-1,))[..., f_idx],
                                   mode="drop")
        return tf.reshape(lead + (geom_t.num_chunks, V, geom_t.K))

    spmm = steered_spmm(st, geom, backend="pallas", interpret=interpret)
    spmm_t = steered_spmm(st_t, geom_t, backend="pallas", interpret=interpret)
    dQ = spmm(K_mat, vals=de)
    dK = spmm_t(Q, vals=to_transpose(de))
    dVf = spmm_t(dOut, vals=to_transpose(alpha))
    return dQ, dK, dVf


@_observed_build("gat_message")
def make_gat_message_fn(pcsr: PCSR, pcsr_t: Optional[PCSR] = None, *,
                        backend: str | None = None,
                        interpret: bool | None = None, slope: float = 0.2):
    """Differentiable fused GAT message ``f(Q, K, Vf) -> (n_rows, d)``
    over one PCSR: ``gat_forward`` on its steering.  Multi-head: rank-3
    ``(H, n, d)`` operands return ``(H, n_rows, d)`` — the Pallas backend
    batches every head through one call of each kernel (the SpMM packs
    heads that fit one lane tile into the lanes of each gathered row).

    Backends (``None`` resolves from the platform, see
    ``repro.kernels.resolve_backend``): the engine is returned as-is,
    natively differentiable; Pallas wraps the two-kernel forward in a
    ``custom_vjp`` whose backward is ``gat_backward`` — all kernels, no
    engine fallback.  The transpose PCSR is built once
    (``core.pcsr.transpose_pcsr``) when not supplied — pass
    ``ParamSpMMOperator.pcsr_t`` to share the cached one."""
    backend, interpret = resolve_backend(backend, interpret)

    def steering(p, H):
        return p.steering(H, covered=True), p.steering(H)

    def forward(Q, K_mat, Vf):
        return gat_forward(*steering(pcsr, _heads(Q)), Q, K_mat, Vf,
                           geom=pcsr, backend=backend, interpret=interpret,
                           slope=slope)

    if backend != "pallas":
        return lambda Q, K_mat, Vf: forward(Q, K_mat, Vf)[0]

    from .pcsr import slot_transfer_map, transpose_pcsr
    if pcsr_t is None:
        pcsr_t = transpose_pcsr(pcsr)
    f_idx, t_idx = slot_transfer_map(pcsr, pcsr_t)

    @jax.custom_vjp
    def f(Q, K_mat, Vf):
        return forward(Q, K_mat, Vf)[0]

    def f_fwd(Q, K_mat, Vf):
        out, res = forward(Q, K_mat, Vf)
        return out, (Q, K_mat, Vf) + res

    def f_bwd(res, dOut):
        H = _heads(dOut)
        return gat_backward(*steering(pcsr, H),
                            pcsr_t.steering(H, covered=True), f_idx, t_idx,
                            res, dOut, geom=pcsr, geom_t=pcsr_t,
                            interpret=interpret, slope=slope)

    f.defvjp(f_fwd, f_bwd)
    return f


def make_spmm_fn(pcsr: PCSR, pcsr_t: Optional[PCSR] = None, *,
                 backend: str | None = None, interpret: bool | None = None):
    """Build a differentiable ``f(B) = A·B`` closed over PCSR arrays.

    backend: "engine" (pure JAX) or "pallas" (TPU kernel); ``None``
    resolves from the platform (``repro.kernels.resolve_backend``).  The
    VJP uses the transpose PCSR when given, otherwise gradients flow
    through the engine's gather/scatter directly.
    """
    backend, interpret = resolve_backend(backend, interpret)
    fwd = steered_spmm(pcsr.steering(covered=True), pcsr, backend=backend,
                       interpret=interpret)
    if pcsr_t is None:
        return lambda B: fwd(B)     # plain A·B: no fusion surface
    bwd = steered_spmm(pcsr_t.steering(covered=True), pcsr_t,
                       backend=backend, interpret=interpret)

    @jax.custom_vjp
    def f(B):
        return fwd(B)

    def f_fwd(B):
        return fwd(B), None

    def f_bwd(_, dC):
        return (bwd(dC),)

    f.defvjp(f_fwd, f_bwd)
    return f


def apply_epilogue(out, scale=None, bias=None, activation: str = "none",
                   slope: float = 0.2, residual=None):
    """The SpMM epilogue semantics, in plain JAX:
    ``act(scale[:, None] ⊙ out + bias[None, :] + residual)``.  Single
    source of truth for what the Pallas kernel's fused epilogue computes
    — the engine backend and the per-shard distributed branches run this
    (XLA fuses it into the surrounding program), the Pallas kernel
    applies the same ops to the VMEM-resident output block before
    write-back.  ``residual`` is a dense (n, d) addend (GIN's ``(1+ε)h``
    term)."""
    if scale is not None:
        out = out * scale[:, None]
    if bias is not None:
        out = out + bias[None, :]
    if residual is not None:
        out = out + residual
    if activation == "relu":
        out = jax.nn.relu(out)
    elif activation == "leaky_relu":
        out = jax.nn.leaky_relu(out, negative_slope=slope)
    elif activation != "none":
        raise ValueError(f"unknown epilogue activation {activation!r}")
    return out


def epilogue_grad(out, dOut, activation: str = "none", slope: float = 0.2):
    """d(pre-activation) of the fused epilogue from its *output*: both
    relu and leaky_relu preserve sign, so act' is recoverable from ``out``
    alone.  The one backward for ``apply_epilogue``'s activations — the
    single-device and distributed fused custom_vjps both call this, so
    the derivative (and the slope constant) cannot drift between them."""
    if activation == "relu":
        return jnp.where(out > 0, dOut, 0.0)
    if activation == "leaky_relu":
        return jnp.where(out >= 0, dOut, slope * dOut)
    if activation != "none":
        raise ValueError(f"unknown epilogue activation {activation!r}")
    return dOut


def engine_spmm_fused(pcsr: PCSR, B, *, scale=None, bias=None,
                      residual=None, activation: str = "none"):
    """act(scale ⊙ (A·B) + bias + residual) on the jit'd JAX engine — the
    reference semantics of the fused-epilogue kernel, natively
    differentiable."""
    return apply_epilogue(engine_spmm(pcsr, B), scale, bias, activation,
                          residual=residual)


def make_fused_spmm_fn(pcsr: PCSR, pcsr_t: Optional[PCSR] = None, *,
                       backend: str | None = None,
                       interpret: bool | None = None):
    """Build the epilogue-fused aggregation closure
    ``fused(B, scale=None, bias=None, activation="none", residual=None)
    -> (n, d)`` computing ``act(scale ⊙ (A·B) + bias + residual)`` — one
    kernel on the Pallas backend (scale/bias/residual/activation applied
    to the VMEM-resident output block on its last visit) instead of
    kernel + 2–3 XLA elementwise passes over the (n, d) output.  The
    dense ``residual`` operand is what lets GIN's ``(1+ε)h + A·h``
    aggregation run as ONE kernel.

    Differentiable in ``B``, ``bias``, and ``residual`` (``scale`` is
    graph data — degree norms — and is treated as a constant): with
    ``pcsr_t`` both backends run a ``custom_vjp`` whose backward is

        dpre  = dOut ⊙ act'(out)          (act' recovered from out: both
                                           relu and leaky_relu preserve sign)
        dbias = Σ_rows dpre
        dresidual = dpre                   (the add is linear)
        dB    = SpMM(pcsrᵀ, scale ⊙ dpre)  (transpose-PCSR SpMM)

    — the same transpose path the plain ``make_spmm_fn`` takes, so fusing
    never swaps the optimized backward for a generic scatter transpose.
    Without ``pcsr_t`` the engine path falls back to native autodiff; the
    Pallas path requires it for gradients.
    """
    backend, interpret = resolve_backend(backend, interpret)
    fwd = steered_spmm(pcsr.steering(covered=True), pcsr, backend=backend,
                       interpret=interpret)

    if backend != "pallas" and pcsr_t is None:
        return fwd                         # native autodiff
    bwd = None if pcsr_t is None else steered_spmm(
        pcsr_t.steering(covered=True), pcsr_t, backend=backend,
        interpret=interpret)

    vjps: dict = {}                # one custom_vjp per activation

    def _vjp(activation: str):
        # scale/bias/residual enter as primals (None stays a None pytree
        # leaf) so a traced scale never leaks into the vjp closure;
        # scale's cotangent is zero — degree norms are graph data, not a
        # trained parameter.
        @jax.custom_vjp
        def f(B, scale, bias, residual):
            return fwd(B, scale=scale, bias=bias, activation=activation,
                       residual=residual)

        def f_fwd(B, scale, bias, residual):
            out = fwd(B, scale=scale, bias=bias, activation=activation,
                      residual=residual)
            return out, (out, scale, bias, residual is not None)

        def f_bwd(res, dOut):
            out, scale, bias, has_resid = res
            if pcsr_t is None:
                raise ValueError("fused SpMM backward needs the transpose "
                                 "PCSR — build the operator with "
                                 "build_transpose=True")
            dpre = epilogue_grad(out, dOut, activation)
            dbias = None if bias is None else dpre.sum(axis=0)
            dresid = dpre if has_resid else None
            dcb = dpre if scale is None else dpre * scale[:, None]
            dB = bwd(dcb)
            dscale = None if scale is None else jnp.zeros_like(scale)
            return dB, dscale, dbias, dresid

        f.defvjp(f_fwd, f_bwd)
        return f

    def fused(B, scale=None, bias=None, activation: str = "none",
              residual=None):
        if activation not in vjps:
            vjps[activation] = _vjp(activation)
        return vjps[activation](
            B, None if scale is None else jnp.asarray(scale),
            None if bias is None else jnp.asarray(bias),
            None if residual is None else jnp.asarray(residual))
    return fused


class ParamSpMMOperator:
    """User-facing operator: holds forward + transpose PCSR for one sparse
    matrix under one ⟨W,F,V,S⟩ configuration.  ``op(B)`` is the plain
    SpMM; ``op.fused(B, scale=, bias=, activation=, residual=)`` the
    epilogue-fused aggregation (one kernel per GCN — or, via the
    residual addend, GIN — layer on the Pallas backend)."""

    def __init__(self, csr: CSRMatrix, config: SpMMConfig, *,
                 backend: str | None = None, interpret: bool | None = None,
                 build_transpose: bool = True):
        self.csr = csr
        self.config = config
        backend, interpret = resolve_backend(backend, interpret)
        self.backend = backend
        self.interpret = interpret
        self.pcsr = build_pcsr(csr.indptr, csr.indices, csr.data,
                               csr.n_rows, csr.n_cols, config)
        self.pcsr_t = None
        if build_transpose:
            t = csr.transpose()
            self.pcsr_t = build_pcsr(t.indptr, t.indices, t.data,
                                     t.n_rows, t.n_cols, config)
        self._fn = make_spmm_fn(self.pcsr, self.pcsr_t,
                                backend=backend, interpret=interpret)
        self.fused = make_fused_spmm_fn(self.pcsr, self.pcsr_t,
                                        backend=backend, interpret=interpret)

    def __call__(self, B):
        return self._fn(B)
