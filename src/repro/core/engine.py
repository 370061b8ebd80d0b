"""ParamSpMM computing engine (paper Alg. 2) — pure-JAX implementation.

Same PCSR traversal as the Pallas kernel, expressed as gather + scatter-add
so it jit-compiles natively on any backend (CPU benchmarking, GNN training)
and is differentiable.  The Pallas kernel in ``repro.kernels.paramspmm`` is
the TPU artifact; both are validated against ``ref.py``.

``make_spmm_fn`` builds the differentiable operator: the backward SpMM
``dB = Aᵀ·dC`` runs a second PCSR built for ``Aᵀ`` — GNN training performs
forward and backward SpMM exactly as the paper's PyTorch extension does.

``make_gat_message_fn`` builds the attention-GNN operator over the same
PCSR: SDDMM → LeakyReLU → edge softmax → SpMM, single- or multi-head.  On
the Pallas backend both the forward (fused softmax epilogue) and the
dedicated backward (transpose-PCSR SpMMs) run entirely in kernels; see the
function docstring and docs/OPERATORS.md for the exact pipelines.
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
    """The GAT attention step shared by every backend: scale raw SDDMM
    scores by 1/√d_k, LeakyReLU(slope), softmax over each destination
    row's edge set.  Single source of truth — the single-device message
    fn and the distributed per-shard branches (``repro.dist.spmm``) must
    stay semantically identical."""
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


@_observed_build("gat_message")
def make_gat_message_fn(pcsr: PCSR, pcsr_t: Optional[PCSR] = None, *,
                        backend: str | None = None,
                        interpret: bool | None = None, slope: float = 0.2):
    """Differentiable fused GAT message ``f(Q, K, Vf) -> (n_rows, d)``:
    SDDMM → LeakyReLU → softmax-over-edges → SpMM, all over one PCSR.

    Scores are scaled by 1/√d_k (dot-product attention) then passed through
    LeakyReLU(slope) as in GAT.  Multi-head: rank-3 ``(H, n, d)`` operands
    return ``(H, n_rows, d)`` — the Pallas backend batches every head
    through one head-tiled kernel call (a single compilation), the engine
    backend vmaps its jitted path.

    Backends (``None`` resolves from the platform, see
    ``repro.kernels.resolve_backend``):

    * ``"engine"`` — the pure-JAX path, returned as-is: natively
      differentiable, no ``custom_vjp`` required.
    * ``"pallas"`` — the **two-kernel forward**: the fused SDDMM→softmax
      kernel (``sddmm_softmax_stats``: row max/normalizer accumulated in
      the kernel epilogue while the score block is VMEM resident) hands
      (logits, rowmax, rowsum) straight to the SpMM kernel's softmax
      *prologue* (``paramspmm_with_vals(stats=...)``), which rebuilds
      α = exp(logit − max)/Σ in-register while loading vals — NO
      interstitial elementwise pass and α is never materialized in HBM.

      The backward is flash-style recompute: residuals are only the raw
      logits + the two tile-aligned row-stat arrays
      ((n_blocks·SUBLANES, LANES), the kernel's native layout) — the
      (C, V, K) α residual is dropped and α is recomputed from the stats
      where the vjp needs it.  The pipeline is dedicated all-Pallas — no engine
      fallback:

        α   = exp(logits − rowmax)/rowsum       (recompute, no residual)
        dα  = SDDMM(pcsr, dOut, Vf)            (dα_ij = dOut_i·Vf_j)
        dx  = α ⊙ (dα − Σ_row α·dα)            (softmax vjp, per-slot)
        de  = dx · scale · LeakyReLU'(x)        (activation chain)
        dQ  = SpMM(pcsr,  de, K)               (row-gather of keys)
        dK  = SpMM(pcsrᵀ, deᵀ, Q)              (transpose-PCSR SpMM)
        dVf = SpMM(pcsrᵀ, αᵀ, dOut)            (transpose-PCSR SpMM)

      The transpose PCSR is built once (``core.pcsr.transpose_pcsr``) when not
      supplied — pass ``ParamSpMMOperator.pcsr_t`` to share the cached one
      — and slot tensors move between the two layouts through a
      precomputed ``slot_transfer_map`` gather/scatter.
    """
    backend, interpret = resolve_backend(backend, interpret)
    arrs = pcsr.to_jax()
    cfg = pcsr.config
    V, R, K, n_blocks = cfg.V, cfg.R, pcsr.K, pcsr.n_blocks
    n_rows = pcsr.n_rows
    mask = arrs["vals"] != 0
    rows = _slot_rows(arrs["lrow"], arrs["trow"], V=V, R=R, K=K)

    def _attend(scores, Q):
        return attend_scores(scores, mask, rows, n_blocks * R,
                             dim_k=Q.shape[1], slope=slope)

    def engine_path(Q, K_mat, Vf):
        scores = _engine_sddmm(arrs["colidx"], arrs["lrow"], arrs["trow"],
                               arrs["vals"], Q, K_mat, V=V, R=R, K=K)
        alpha = _attend(scores, Q)
        return _engine(arrs["colidx"], arrs["lrow"], arrs["trow"], alpha,
                       Vf, V=V, R=R, K=K, n_blocks=n_blocks, n_rows=n_rows)

    def engine_fn(Q, K_mat, Vf):
        if jnp.ndim(Q) == 3:
            return jax.vmap(engine_path)(Q, K_mat, Vf)
        return engine_path(Q, K_mat, Vf)

    if backend != "pallas":
        return engine_fn            # natively differentiable, no vjp needed

    from repro.kernels.paramspmm.ops import paramspmm_with_vals
    from repro.kernels.sddmm.ops import (normalize_from_stats,
                                         sddmm as _sddmm_call,
                                         sddmm_softmax_stats, unpack_stats)

    from .pcsr import slot_transfer_map, transpose_pcsr
    if pcsr_t is None:
        pcsr_t = transpose_pcsr(pcsr)
    f_idx, t_idx = slot_transfer_map(pcsr, pcsr_t)
    n_tslots = pcsr_t.num_chunks * cfg.V * pcsr_t.K
    flat_rows = rows.reshape(-1)

    def _to_transpose(x):
        """Re-lay a (..., C, V, K) slot tensor onto the Aᵀ PCSR's slots."""
        lead = x.shape[:-3]
        tf = jnp.zeros(lead + (n_tslots,), x.dtype)
        tf = tf.at[..., t_idx].set(x.reshape(lead + (-1,))[..., f_idx])
        return tf.reshape(lead + (pcsr_t.num_chunks, cfg.V, pcsr_t.K))

    def _rowsum(x):
        """Per-slot broadcast of Σ over each destination row's slots."""
        s = jax.ops.segment_sum(x.reshape(-1), flat_rows,
                                num_segments=n_blocks * R)
        return s[flat_rows].reshape(x.shape)

    def _alpha_1h(logits, rowmax, rowsum):
        """Flash-style α recompute from the stats residuals (one head) —
        the single normalize implementation shared with sddmm/ops, so the
        masked-slot/empty-row guard convention cannot drift."""
        return normalize_from_stats(logits, rowmax, rowsum, arrs["lrow"],
                                    arrs["trow"], R=R, V=V, K=K)

    def _alpha(logits, rowmax, rowsum):
        rm = unpack_stats(rowmax, R)       # tile-aligned → dense (·, R)
        rs = unpack_stats(rowsum, R)
        if logits.ndim == 4:                            # (H, C, V, K)
            H = logits.shape[0]
            return jax.vmap(_alpha_1h)(logits, rm.reshape(H, -1, R),
                                       rs.reshape(H, -1, R))
        return _alpha_1h(logits, rm, rs)

    def fwd_path(Q, K_mat, Vf):
        logits, rowmax, rowsum = sddmm_softmax_stats(
            pcsr, Q, K_mat, slope=slope, interpret=interpret)
        out = paramspmm_with_vals(pcsr, logits, Vf, stats=(rowmax, rowsum),
                                  interpret=interpret)
        return out, (Q, K_mat, Vf, logits, rowmax, rowsum)

    @jax.custom_vjp
    def f(Q, K_mat, Vf):
        return fwd_path(Q, K_mat, Vf)[0]

    def f_fwd(Q, K_mat, Vf):
        return fwd_path(Q, K_mat, Vf)

    def f_bwd(res, dOut):
        Q, K_mat, Vf, logits, rowmax, rowsum = res
        alpha = _alpha(logits, rowmax, rowsum)          # recompute, cheap
        scale = 1.0 / jnp.sqrt(jnp.asarray(Q.shape[-1], dOut.dtype))
        dalpha = _sddmm_call(pcsr, dOut, Vf, interpret=interpret)
        rsum = (jax.vmap(_rowsum) if alpha.ndim == 4 else _rowsum)
        dx = alpha * (dalpha - rsum(alpha * dalpha))       # softmax vjp
        # LeakyReLU' from the saved logits: LeakyReLU preserves sign, so
        # sign(logits) = sign(pre-activation); masked slots (logit −inf)
        # have dx = 0, so the slope branch they fall into is inert.
        de = dx * scale * jnp.where(logits >= 0, 1.0, slope)
        dQ = paramspmm_with_vals(pcsr, de, K_mat, interpret=interpret)
        dK = paramspmm_with_vals(pcsr_t, _to_transpose(de), Q,
                                 interpret=interpret)
        dVf = paramspmm_with_vals(pcsr_t, _to_transpose(alpha), dOut,
                                  interpret=interpret)
        return dQ, dK, dVf

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
    if backend == "pallas":
        from repro.kernels.paramspmm.ops import paramspmm as _fwd_call
        fwd = lambda B: _fwd_call(pcsr, B, interpret=interpret)
    else:
        fwd = lambda B: engine_spmm(pcsr, B)

    if pcsr_t is None:
        return fwd

    if backend == "pallas":
        from repro.kernels.paramspmm.ops import paramspmm as _bwd_call
        bwd = lambda dC: _bwd_call(pcsr_t, dC, interpret=interpret)
    else:
        bwd = lambda dC: engine_spmm(pcsr_t, dC)

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
    if backend == "pallas":
        from repro.kernels.paramspmm.ops import paramspmm

        def fwd_call(B, scale, bias, residual, activation):
            return paramspmm(pcsr, B, scale=scale, bias=bias,
                             residual=residual, activation=activation,
                             interpret=interpret)

        def bwd_call(dC):
            return paramspmm(pcsr_t, dC, interpret=interpret)
    else:
        def fwd_call(B, scale, bias, residual, activation):
            return engine_spmm_fused(pcsr, B, scale=scale, bias=bias,
                                     residual=residual,
                                     activation=activation)

        def bwd_call(dC):
            return engine_spmm(pcsr_t, dC)

    if backend != "pallas" and pcsr_t is None:
        def fused(B, scale=None, bias=None, activation: str = "none",
                  residual=None):
            return fwd_call(B, scale, bias, residual,
                            activation)  # native autodiff
        return fused

    vjps: dict = {}                # one custom_vjp per activation

    def _vjp(activation: str):
        # scale/bias/residual enter as primals (None stays a None pytree
        # leaf) so a traced scale never leaks into the vjp closure;
        # scale's cotangent is zero — degree norms are graph data, not a
        # trained parameter.
        @jax.custom_vjp
        def f(B, scale, bias, residual):
            return fwd_call(B, scale, bias, residual, activation)

        def f_fwd(B, scale, bias, residual):
            out = fwd_call(B, scale, bias, residual, activation)
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
            dB = bwd_call(dcb)
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
