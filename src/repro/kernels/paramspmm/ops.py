"""The ParamSpMM kernel's one dispatch, ``spmm_steered``, and the PCSR
entry points ``paramspmm(pcsr, B)`` / ``paramspmm_with_vals``.

``spmm_steered`` is the only caller of the kernel: it picks the lane tile
(``SpMMConfig.dblk_for``), pads the operand's columns, appends coverage
chunks to call-time values, packs heads into lanes (or tiles them where
they do not fit one lane tile), packs the epilogue operands and crops
the output.  Every operator above the kernels (``core.engine``, and
through it ``dist``, ``serve`` and ``dynamic``) reaches the kernel
through it, over constant or traced steering arrays.

All dispatch goes through *covered* steering arrays
(``PCSR.steering(covered=True)``): every output block — including empty
ones — is visited and zero-initialized by the kernel's own ``init`` path,
so no post-kernel unvisited-block mask pass remains.

Fusion surface (see ``kernel.py``):

* ``stats=(rowmax, rowsum)`` — softmax *prologue*: ``vals`` are raw
  logits (masked slots −inf) and α is computed in-register from the
  per-row stats.  The GAT hot path feeds the stats SDDMM's stats straight
  in: two kernels, zero interstitial elementwise pass.
* ``scale=, bias=, residual=, activation=`` — fused *epilogue*: per-row
  degree-norm scale, per-feature bias, dense residual add (GIN's
  ``(1+ε)h`` operand), activation applied on the last visit of each
  VMEM-resident output block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.pcsr import PCSR, LANES, SUBLANES
from .kernel import paramspmm_kernel


def _pad_cols(B, dblk: int):
    dim = B.shape[1]
    dim_pad = -(-dim // dblk) * dblk
    if dim_pad != dim:
        B = jnp.pad(B, ((0, 0), (0, dim_pad - dim)))
    return B, dim_pad


def _pack_scale(x, n_blocks: int, R: int):
    """Pack a flat per-row vector (≤ n_blocks·R entries) into the kernel's
    per-row column ``(n_blocks·R, 1)`` — block b's scales are the (R, 1)
    tile at row ``b·R``, broadcast across lanes in the epilogue."""
    return jnp.pad(x.reshape(-1), (0, n_blocks * R - x.size)
                   ).reshape(n_blocks * R, 1)


@functools.partial(jax.jit, static_argnames=(
    "cfg", "n_blocks", "K", "n_rows", "dim", "activation", "interpret"))
def _call(colidx, lrow, trow, init, fini, vals, B, rowmax=None, rowsum=None,
          scale=None, bias=None, residual=None, *, cfg, n_blocks, K, n_rows,
          dim, activation="none", interpret):
    """Pallas dispatch on pre-packed (covered) steering arrays.

    The call gathers ``cfg.dblk_for(dim)`` lanes per row: a ``dim``-wide
    operand that fits one of the pick's dim tiles is padded to whole
    128-lane tiles only, not to ``cfg.dblk``.

    ``scale`` is a flat per-row vector (≤ n_blocks·R entries), ``bias`` a
    flat per-feature vector (≤ dim entries), ``residual`` a dense
    ``(≤ n_rows, dim)`` addend; all are padded here to the kernel's
    tile-aligned block shapes.  ``rowmax``/``rowsum`` are the
    online-softmax stats from the fused SDDMM (vals = raw logits) in its
    native tile-aligned ``(n_blocks·SUBLANES, LANES)`` layout — asserted
    here so a dense ``(n_blocks, R)`` array (which only interpret mode
    would tolerate) fails loudly at trace time.
    """
    stats_shape = (n_blocks * SUBLANES, LANES)
    for name, arr in (("rowmax", rowmax), ("rowsum", rowsum)):
        assert arr is None or arr.shape == stats_shape, (
            f"{name} must be tile-aligned {stats_shape} "
            f"(the fused SDDMM's native layout), got {arr.shape}")
    R, dblk = cfg.R, cfg.dblk_for(dim)
    B_padded, dim_pad = _pad_cols(B, dblk)
    if scale is not None:
        scale = _pack_scale(scale, n_blocks, R)
    if bias is not None:
        bias = jnp.pad(bias.reshape(-1), (0, dim_pad - bias.size))[None, :]
        bias = jnp.pad(bias, ((0, SUBLANES - 1), (0, 0)))   # tile-aligned
    if residual is not None:
        residual = jnp.pad(residual,
                           ((0, n_blocks * R - residual.shape[0]),
                            (0, dim_pad - residual.shape[1])))
    out = paramspmm_kernel(colidx, lrow, trow, init, fini, vals, B_padded,
                           n_blocks=n_blocks, R=R, V=cfg.V, K=K,
                           dblk=dblk, rowmax=rowmax, rowsum=rowsum,
                           scale=scale, bias=bias, residual=residual,
                           activation=activation, width=dim,
                           widest=cfg.dblk, interpret=interpret)
    return out[:n_rows, :dim]


@functools.partial(jax.jit, static_argnames=(
    "H", "cfg", "n_blocks", "K", "n_rows", "dim", "activation",
    "interpret"))
def _call_heads(colidx, lrow, trow, init, fini, vals, B, rowmax=None,
                rowsum=None, *, H, cfg, n_blocks, K, n_rows, dim,
                activation="none", interpret):
    out = _call(colidx, lrow, trow, init, fini,
                vals.reshape((H * vals.shape[1],) + vals.shape[2:]),
                B.reshape(H * B.shape[1], B.shape[2]),
                rowmax, rowsum,
                cfg=cfg, n_blocks=H * n_blocks, K=K,
                n_rows=H * n_blocks * cfg.R, dim=dim, activation=activation,
                interpret=interpret)
    return out.reshape(H, n_blocks * cfg.R, dim)[:, :n_rows]


def _spread_stats(stats, H: int, d: int, n_blocks: int, R: int, dblk: int):
    """Per-head row stats in the stats SDDMM's tile-aligned layout
    ``(H·n_blocks·SUBLANES, LANES)`` as the packed kernel's per-row lane
    vectors ``(n_blocks·R, dblk)``: head h's stat of row r in lanes
    ``h·d … h·d+d−1`` of row r, 0 past ``H·d``."""
    dense = stats.reshape(H, n_blocks, SUBLANES, LANES)[:, :, 0, :R]
    lanes = jnp.repeat(dense.reshape(H, n_blocks * R).T, d, axis=1)
    return jnp.pad(lanes, ((0, 0), (0, dblk - H * d)))


@functools.partial(jax.jit, static_argnames=(
    "cfg", "n_blocks", "K", "n_rows", "interpret"))
def _call_packed(colidx, lrow, trow, init, fini, vals, B, rowmax=None,
                 rowsum=None, *, cfg, n_blocks, K, n_rows, interpret):
    """Every head of an (H, n, d) operand in one call over one head's
    steering: head h rides lanes ``h·d … h·d+d−1`` of each gathered row
    (``H·d`` ≤ ``cfg.dblk``).  ``vals`` are one head's stored (C, V, K)
    values, every head's, or per-head (H, C, V, K) ones; the stats are
    head-tiled, as the stats SDDMM gives them.  Returns (H, n_rows, d)."""
    H, n, d = B.shape
    R, dblk = cfg.R, cfg.dblk_for(H * d)
    B_lanes, _ = _pad_cols(B.transpose(1, 0, 2).reshape(n, H * d), dblk)
    if vals.ndim == 4:                    # (H, C, V, K) → (C, V, K, H)
        vals = vals.transpose(1, 2, 3, 0)
    if rowmax is not None:
        rowmax, rowsum = (_spread_stats(x, H, d, n_blocks, R, dblk)
                          for x in (rowmax, rowsum))
    out = paramspmm_kernel(colidx, lrow, trow, init, fini, vals, B_lanes,
                           n_blocks=n_blocks, R=R, V=cfg.V, K=K, dblk=dblk,
                           rowmax=rowmax, rowsum=rowsum, width=H * d,
                           widest=cfg.dblk, heads=H, interpret=interpret)
    return out[:n_rows, :H * d].reshape(n_rows, H, d).transpose(1, 0, 2)


def _pad_chunk_vals(vals, n_extra: int, fill: float):
    """Append ``n_extra`` coverage chunks to a (..., C, V, K) slot tensor."""
    if n_extra == 0:
        return vals
    pad = [(0, 0)] * vals.ndim
    pad[-3] = (0, n_extra)
    return jnp.pad(vals, pad, constant_values=fill)


def spmm_steered(steer, B, vals=None, *, cfg, n_blocks: int, K: int,
                 n_rows: int, stats=None, scale=None, bias=None,
                 residual=None, activation: str = "none",
                 interpret: bool | None = None):
    """The one dispatch of the ParamSpMM kernel:
    ``act(scale ⊙ (A·B) + bias + residual)`` over *covered* steering
    arrays ``steer`` (``PCSR.steering(H, covered=True)``'s keys and
    layout, as host constants or traced values) with static geometry
    ``cfg``, ``n_blocks``, ``K`` and ``n_rows``.

    ``B`` of shape (n, d) runs one head; (H, n, d) runs every head in one
    kernel call and returns (H, n_rows, d).  Where several heads fit one
    of the pick's lane tiles (H·d ≤ ``cfg.dblk``) they are packed side by
    side into the lanes of each gathered row and the call runs head 0's
    chunks (the first ``1/H`` of head-tiled steering); wider heads run
    the head-tiled chunks, one row per head.
    ``vals=None`` uses the values in ``steer``; call-time ``vals``
    ((C, V, K), or (H, C, V, K)) cover the real chunks only, and the
    coverage chunks are appended here: −inf under ``stats``, else 0.

    ``stats=(rowmax, rowsum)`` enables the fused softmax **prologue**:
    ``vals`` are then the raw logits of the stats SDDMM (masked slots
    −inf) and α = exp(logit − rowmax)/rowsum is computed in-register.
    Stats use the stats SDDMM's tile-aligned layout
    ``(H·n_blocks·SUBLANES, LANES)`` (``repro.kernels.sddmm.ops.
    unpack_stats`` gives the dense view).

    ``scale``/``bias``/``residual``/``activation`` enable the fused
    **epilogue** (single-head only): per-row scale (flat, ≤ n_rows),
    per-feature bias (flat, ≤ d), dense residual addend ((n, d) — GIN's
    ``(1+ε)h`` term rides the VMEM-resident output block), then
    activation, applied on the last visit of each output block.
    """
    B = jnp.asarray(B)
    if stats is not None and vals is None:
        # the prologue interprets vals as logits; stored edge weights (and
        # the 0-valued coverage chunks) are NOT logits — exp(0 − stat)
        # would silently turn padding into weight
        raise ValueError("stats= requires explicit logits as vals "
                         "(from sddmm_softmax_stats), not the stored "
                         "PCSR values")
    fill = -jnp.inf if stats is not None else 0.0
    rowmax, rowsum = stats if stats is not None else (None, None)
    if B.ndim == 3:                       # (H, n, d) head batch
        if (scale is not None or bias is not None or residual is not None
                or activation != "none"):
            raise NotImplementedError("epilogue fusion is single-head")
        H, d = B.shape[0], B.shape[2]
        C_cov = steer["trow"].shape[0] // H
        if vals is not None:
            vals = jnp.asarray(vals)
            if vals.ndim != 4 or vals.shape[0] != H:
                raise ValueError(f"multi-head vals must be (H={H}, C, V, K), "
                                 f"got {vals.shape}")
            vals = _pad_chunk_vals(vals, C_cov - vals.shape[1], fill)
        if 1 < H and H * d <= cfg.dblk:   # heads packed: head 0's chunks
            h0 = {k: a[:C_cov * K] if k in ("colidx", "lrow") else a[:C_cov]
                  for k, a in steer.items()}
            return _call_packed(h0["colidx"], h0["lrow"], h0["trow"],
                                h0["init"], h0["fini"],
                                h0["vals"] if vals is None else vals, B,
                                rowmax, rowsum, cfg=cfg, n_blocks=n_blocks,
                                K=K, n_rows=n_rows, interpret=interpret)
        if vals is None:                  # stored values, same for each head
            vals = steer["vals"].reshape(H, C_cov, cfg.V, K)
        return _call_heads(steer["colidx"], steer["lrow"], steer["trow"],
                           steer["init"], steer["fini"], vals, B, rowmax,
                           rowsum, H=H, cfg=cfg, n_blocks=n_blocks, K=K,
                           n_rows=n_rows, dim=B.shape[2],
                           interpret=interpret)
    C_cov = steer["trow"].shape[0]
    if vals is None:
        vals = steer["vals"]
    else:
        vals = _pad_chunk_vals(jnp.asarray(vals),
                               C_cov - jnp.shape(vals)[-3], fill)
    return _call(steer["colidx"], steer["lrow"], steer["trow"],
                 steer["init"], steer["fini"], vals, B, rowmax, rowsum,
                 None if scale is None else jnp.asarray(scale),
                 None if bias is None else jnp.asarray(bias),
                 None if residual is None else jnp.asarray(residual),
                 cfg=cfg, n_blocks=n_blocks, K=K, n_rows=n_rows,
                 dim=B.shape[1], activation=activation, interpret=interpret)


def paramspmm(pcsr: PCSR, B, *, scale=None, bias=None, residual=None,
              activation: str = "none", interpret: bool | None = None):
    """C = act(scale ⊙ (A·B) + bias + residual) where A is held as PCSR —
    the epilogue operands default to the identity (plain A·B).  Pallas
    path (``interpret=None``: compiled on a TPU, interpreted elsewhere)."""
    return paramspmm_with_vals(pcsr, None, B, scale=scale, bias=bias,
                               residual=residual, activation=activation,
                               interpret=interpret)


def paramspmm_with_vals(pcsr: PCSR, vals, B, *, stats=None, scale=None,
                        bias=None, residual=None, activation: str = "none",
                        interpret: bool | None = None):
    """``spmm_steered`` over a PCSR's own covered steering: SpMM over A's
    *pattern* with per-slot values supplied at call time (``vals=None``
    uses the stored ones) — the aggregation step of attention GNNs, where
    the topology is fixed but the edge weights change every step."""
    B = jnp.asarray(B)
    H = B.shape[0] if B.ndim == 3 else 1
    return spmm_steered(pcsr.steering(H, covered=True), B, vals,
                        cfg=pcsr.config, n_blocks=pcsr.n_blocks, K=pcsr.K,
                        n_rows=pcsr.n_rows, stats=stats, scale=scale,
                        bias=bias, residual=residual, activation=activation,
                        interpret=interpret)
