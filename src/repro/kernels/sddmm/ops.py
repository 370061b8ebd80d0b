"""The SDDMM kernels' dispatches and their PCSR entry points.

Each kernel has one dispatch over steering arrays, constant or traced —
``sddmm_steered`` (raw masked scores) and ``sddmm_softmax_steered`` (the
stats form) — which pads the operands and picks the lane tile
(``_static``); the PCSR entry points below and every operator above the
kernels reach the kernels only through them.  Both are multi-head:
``(H, n, d)`` operands run every head in ONE kernel call over head-tiled
steering arrays (``PCSR.steering(H)``), so multi-head GAT compiles once.

* ``sddmm(pcsr, Q, K)`` — raw masked edge scores in slot layout;
* ``sddmm_softmax_stats(pcsr, Q, K)`` — the fused GAT attention front
  half in *stats form*: one kernel pass producing raw logits (masked
  slots −inf) + per-row online-softmax stats, consumed directly by the
  ParamSpMM softmax prologue — ZERO elementwise passes between the two
  kernels of the GAT forward;
* ``sddmm_softmax(pcsr, Q, K)`` — the materialized-α reference form
  (stats pass + one elementwise normalize), kept for validation and for
  callers that genuinely need α as a tensor.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.pcsr import PCSR, LANES, SUBLANES
from repro.kernels.paramspmm.ops import _pad_cols


def stats_rows(n_blocks: int) -> int:
    """Leading extent of the tile-aligned stats layout: one full
    ``(SUBLANES, LANES)`` f32 tile per output block."""
    return n_blocks * SUBLANES


def unpack_stats(stats, R: int):
    """Dense ``(..., n_blocks, R)`` view of tile-aligned kernel stats.

    The fused SDDMM keeps per-row softmax stats in one aligned
    ``(SUBLANES, LANES)`` tile per block — row r of block b at
    ``[b·SUBLANES, r]`` — so the stats BlockSpec is exactly one hardware
    tile, as the TPU compiler requires.  Plain-JAX consumers (the reference
    normalize, the flash-recompute backward, the distributed GAT
    branches) call this to recover the dense view."""
    lead = stats.shape[:-2]
    nb = stats.shape[-2] // SUBLANES
    return stats.reshape(lead + (nb, SUBLANES, LANES))[..., 0, :R]


def pack_stats(dense, R: int):
    """Inverse of ``unpack_stats``: lay a dense ``(..., n_blocks, R)``
    per-row stat onto the kernel's tile-aligned layout (zeros elsewhere —
    only sublane 0 / lanes < R are ever read)."""
    lead = dense.shape[:-2]
    nb = dense.shape[-2]
    out = jnp.zeros(lead + (nb, SUBLANES, LANES), dense.dtype)
    out = out.at[..., 0, :R].set(dense)
    return out.reshape(lead + (nb * SUBLANES, LANES))


def _pad_q(Q, n_rows_pad: int, dblk: int):
    """Pad a (..., n, d) query stack to (..., n_rows_pad, J·dblk) rows/lanes."""
    Qp, _ = _pad_cols(Q.reshape(-1, Q.shape[-1]), dblk)
    Qp = Qp.reshape(Q.shape[:-1] + (Qp.shape[-1],))
    pad = [(0, 0)] * (Q.ndim - 2) + [(0, n_rows_pad - Q.shape[-2]), (0, 0)]
    return jnp.pad(Qp, pad)


@functools.partial(jax.jit, static_argnames=(
    "H", "n_blocks", "R", "W", "V", "K", "dblk", "interpret"))
def _call(colidx, lrow, trow, vals, Q, K_mat, *, H, n_blocks, R, W, V, K,
          dblk, interpret):
    from .kernel import sddmm_kernel
    Qp = _pad_q(Q, n_blocks * R, dblk).reshape(H * n_blocks * R, -1)
    Kp, _ = _pad_cols(K_mat.reshape(-1, K_mat.shape[-1]), dblk)
    scores = sddmm_kernel(colidx, lrow, trow, Qp, Kp,
                          W=W, V=V, K=K, dblk=dblk, interpret=interpret)
    # sampling mask: padding slots (and explicit zeros) score exactly 0,
    # matching the dense oracle's (A ≠ 0) ⊙ (Q·Kᵀ)
    return jnp.where(vals != 0, scores, 0.0)


def _static(cfg, n_blocks: int, K: int, interpret):
    """The static arguments of an SDDMM kernel call.  The lane tile is
    the pick's ``Dblk``, for every operand width."""
    return dict(n_blocks=n_blocks, R=cfg.R, W=cfg.W, V=cfg.V, K=K,
                dblk=cfg.dblk, interpret=interpret)


def sddmm_steered(steer, Q, K_mat, *, cfg, n_blocks: int, K: int,
                  interpret: bool | None = None):
    """The plain SDDMM kernel's one dispatch: E = (A≠0) ⊙ (Q·Kᵀ) over
    *uncovered* head-tiled steering arrays ``steer``
    (``PCSR.steering(H)``'s keys and layout, constant or traced) for
    (H, n, d) stacks, returning (H, C, V, K) slots."""
    H = Q.shape[0]
    scores = _call(steer["colidx"], steer["lrow"], steer["trow"],
                   steer["vals"], Q, K_mat, H=H,
                   **_static(cfg, n_blocks, K, interpret))
    return scores.reshape(H, -1, cfg.V, K)


def sddmm(pcsr: PCSR, Q, K, *, interpret: bool | None = None):
    """E = (A≠0) ⊙ (Q·Kᵀ) in PCSR slot layout. Pallas path.

    ``Q``/``K`` of shape (n, d) return (C, V, K) slots; (H, n, d) stacks
    return (H, C, V, K) — all heads in a single head-tiled kernel call.
    """
    Q = jnp.asarray(Q)
    K_mat = jnp.asarray(K)
    single = Q.ndim == 2
    if single:
        Q, K_mat = Q[None], K_mat[None]
    scores = sddmm_steered(pcsr.steering(Q.shape[0]), Q, K_mat,
                           cfg=pcsr.config, n_blocks=pcsr.n_blocks,
                           K=pcsr.K, interpret=interpret)
    return scores[0] if single else scores


@functools.partial(jax.jit, static_argnames=(
    "H", "n_blocks", "R", "W", "V", "K", "dblk", "scale", "slope",
    "interpret"))
def _stats_call(colidx, lrow, trow, init, vals, Q, K_mat, *, H, n_blocks, R,
                W, V, K, dblk, scale, slope, interpret):
    from .kernel import sddmm_softmax_kernel
    Qp = _pad_q(Q, n_blocks * R, dblk).reshape(H * n_blocks * R, -1)
    Kp, _ = _pad_cols(K_mat.reshape(-1, K_mat.shape[-1]), dblk)
    return sddmm_softmax_kernel(
        colidx, lrow, trow, init, vals, Qp, Kp,
        n_blocks=H * n_blocks, W=W, V=V, K=K, dblk=dblk,
        scale=scale, slope=slope, interpret=interpret)


def normalize_from_stats(logits, rowmax, rowsum, lrow, trow, *, R, V, K):
    """The *unfused* normalize epilogue: slot → row stats gather + one exp
    and one divide per slot.  The GAT hot path does NOT run this — the
    fused ParamSpMM prologue consumes (logits, rowmax, rowsum) directly.
    It is the ONE shared α-from-stats implementation (masked-slot −inf /
    empty-row guard convention): the reference path behind
    ``sddmm_softmax`` AND the flash-style α recompute in the GAT backward
    (``core.engine.make_gat_message_fn``) — keep the guards here only."""
    C = trow.shape[0]
    rows = (trow[:, None, None].astype(jnp.int32) * R
            + lrow.reshape(C, 1, K) * V
            + jnp.arange(V, dtype=jnp.int32)[None, :, None])
    # Fully-masked/empty rows hold rowmax = −inf, rowsum = 0 — or outright
    # garbage (NaN included) when their block was never visited by the
    # SDDMM.  Both guards must be NaN-proof: ``isfinite`` rejects NaN and
    # ±inf, and ``rowsum > 0`` is False for NaN, so such rows normalize
    # against (0, 1) and their −inf logits come out exactly α = 0 — a
    # ``maximum(rowsum, eps)`` denominator would propagate NaN instead.
    rm = rowmax.reshape(-1)
    rm = jnp.where(jnp.isfinite(rm), rm, 0.0)
    rs = rowsum.reshape(-1)
    denom = jnp.where((rs > 0) & jnp.isfinite(rs), rs, 1.0)
    # masked/padding slots carry logit −inf → exp(−inf − finite) = 0 exact
    return jnp.exp(logits - rm[rows]) / denom[rows]


def sddmm_softmax_steered(steer, Q, K_mat, *, cfg, n_blocks: int, K: int,
                          scale: float | None = None, slope: float = 0.2,
                          interpret: bool | None = None):
    """The stats SDDMM kernel's one dispatch, over *uncovered* head-tiled
    steering (as ``sddmm_steered``) for (H, n, d) stacks: returns
    ``(logits, rowmax, rowsum)``, logits (H, C, V, K), stats in the
    kernel's tile-aligned ``(H·n_blocks·SUBLANES, LANES)`` layout.
    ``scale`` defaults to 1/√d."""
    H = Q.shape[0]
    if scale is None:
        scale = float(1.0 / np.sqrt(Q.shape[-1]))
    logits, rowmax, rowsum = _stats_call(
        steer["colidx"], steer["lrow"], steer["trow"], steer["init"],
        steer["vals"], Q, K_mat, H=H, scale=float(scale), slope=float(slope),
        **_static(cfg, n_blocks, K, interpret))
    return logits.reshape(H, -1, cfg.V, K), rowmax, rowsum


def sddmm_softmax_stats(pcsr: PCSR, Q, K, *, scale: float | None = None,
                        slope: float = 0.2, interpret: bool | None = None):
    """The fused GAT attention front half, *stats form*: one kernel pass
    returning ``(logits, rowmax, rowsum)`` — raw post-LeakyReLU logits in
    slot layout (masked slots −inf) plus the per-row online-softmax
    statistics, exactly the operands ``paramspmm_with_vals(stats=...)``
    consumes in its prologue.  No elementwise normalize runs anywhere:
    the two-kernel GAT forward and the flash-style recompute backward are
    built on this.

    ``scale`` defaults to 1/√d.  Shapes: logits (C, V, K) per (n, d)
    inputs, (H, C, V, K) per (H, n, d); rowmax/rowsum are always the
    kernel-native tile-aligned ``(H·n_blocks·SUBLANES, LANES)`` layout
    (head-tiled blocks, one (8, 128) tile per block) — ``unpack_stats``
    recovers the dense ``(H·n_blocks, R)`` view.
    """
    Q = jnp.asarray(Q)
    K_mat = jnp.asarray(K)
    single = Q.ndim == 2
    if single:
        Q, K_mat = Q[None], K_mat[None]
    logits, rowmax, rowsum = sddmm_softmax_steered(
        pcsr.steering(Q.shape[0]), Q, K_mat, cfg=pcsr.config,
        n_blocks=pcsr.n_blocks, K=pcsr.K, scale=scale, slope=slope,
        interpret=interpret)
    if single:
        logits = logits[0]
    return logits, rowmax, rowsum


@functools.partial(jax.jit, static_argnames=("R", "V", "K", "H"))
def _normalize_heads(logits, rowmax, rowsum, lrow, trow, *, R, V, K, H):
    f = lambda lg, rm, rs: normalize_from_stats(lg, rm, rs, lrow, trow,
                                                R=R, V=V, K=K)
    rm = unpack_stats(rowmax, R)              # (H·n_blocks, R) dense view
    rs = unpack_stats(rowsum, R)
    if H == 1:
        return f(logits[0], rm, rs)[None]
    return jax.vmap(f)(logits, rm.reshape(H, -1, R), rs.reshape(H, -1, R))


def sddmm_softmax(pcsr: PCSR, Q, K, *, scale: float | None = None,
                  slope: float = 0.2, interpret: bool | None = None,
                  with_logits: bool = False):
    """Fused GAT attention weights: softmax_row(LeakyReLU(scale·Q·Kᵀ)) on
    A's sparsity pattern, in PCSR slot layout. Pallas path.

    This is the *materialized-α* form (kernel pass + one elementwise
    normalize): the reference/unfused path.  The GAT hot path uses
    ``sddmm_softmax_stats`` + the SpMM softmax prologue instead and never
    materializes α.  ``scale`` defaults to 1/√d.  Returns ``alpha`` — or
    ``(alpha, logits)`` with ``with_logits=True``, where ``logits`` are the
    post-LeakyReLU scores (masked slots −inf).  Shapes follow ``sddmm``:
    (C, V, K) per (n, d) inputs, (H, C, V, K) per (H, n, d).
    """
    Q = jnp.asarray(Q)
    single = Q.ndim == 2
    logits, rowmax, rowsum = sddmm_softmax_stats(
        pcsr, Q, K, scale=scale, slope=slope, interpret=interpret)
    H = 1 if single else Q.shape[0]
    cfg = pcsr.config
    t = pcsr.steering()           # single-head slot→row map suffices: the
    # head-tiled rows are the single-head rows offset per head
    lg = logits[None] if single else logits
    alpha = _normalize_heads(lg, rowmax, rowsum,
                             jnp.asarray(t["lrow"]), jnp.asarray(t["trow"]),
                             R=cfg.R, V=cfg.V, K=pcsr.K, H=H)
    alpha = alpha[0] if single else alpha
    return (alpha, logits) if with_logits else alpha
