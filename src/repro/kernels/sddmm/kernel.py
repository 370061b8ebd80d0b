"""SDDMM Pallas TPU kernel — PCSR chunk traversal, dot-product reduction.

Mirror image of ``kernels/paramspmm/kernel.py``: the same steering arrays
drive the grid, but the data flow is reversed — instead of scattering
``val · B[col]`` into an output block, each slot *reduces*
``Q[row] · K[col]`` over a ``Dblk``-lane tile into the slot's score.

Grid ``(C, J)`` = (chunks, dim-tiles), run in order.  Per step the
chunk's ``colidx``/``lrow`` slices arrive in SMEM as pipelined blocks
(only ``trow``/``init`` are scalar-prefetched whole), its ``K`` key rows
are gathered HBM → VMEM by the SpMM's two-slot pipelined gather
(``paramspmm.kernel.gather_rows``: the next step's rows — the same chunk
at the next dim tile, or the next chunk after the last — are issued into
the other slot before this step waits for its own), and the query rows
come from the target block's ``(R, Dblk)`` tile of ``Q`` — the paper's
coalesced dense-row access.  Eight slots at a time, the query rows are
staged into an aligned ``(8, Dblk)`` tile, multiplied with the eight
gathered key rows and lane-reduced into an ``(8, 1)`` column of partial
scores.
Scores are kept slot-major — ``(C, K, V)``, slots on sublanes — inside
the kernel and transposed to the PCSR slot layout ``(C, V, K)`` by the
wrappers; the score block stays VMEM-resident across the ``J`` steps of
a chunk, so partial dot products accumulate race-free.

``sddmm_softmax_kernel`` extends the same traversal with a fused edge
softmax epilogue: once a chunk's dot products are complete (its last dim
tile), each score is masked, scaled, LeakyReLU'd, and folded slot by slot
into per-row online softmax statistics kept in two tile-aligned
``(n_blocks·SUBLANES, LANES)`` outputs addressed by ``trow[c]`` — one
full ``(8, 128)`` f32 tile per block, row stats in sublane 0 / lanes
0..R−1 (R ≤ 32 < 128), so the block shape is exactly one hardware tile.
The same consecutive-revisit trick applies: with ``S=True`` a row split
across chunks accumulates its max/normalizer exactly while the stats
tile is VMEM resident.  ``ops.unpack_stats`` recovers the dense
``(n_blocks, R)`` view for plain-JAX consumers.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.pcsr import LANES, SUBLANES
from repro.kernels import resolve_backend
from repro.kernels.paramspmm.kernel import (SEQUENTIAL, gather_rows,
                                            gather_scratch, lane_rows,
                                            load_rows,
                                            record_prefetched_share,
                                            tile_rows)


def _accumulate_scores(colidx_ref, next_colidx_ref, lrow_ref, q_ref, k_hbm,
                       score_ref, kbuf, qbuf, sems, *, V: int, K: int, C: int,
                       J: int, dblk: int):
    """score[k, v] += Q[row_k + v, tile j] · K[colidx_k, tile j] for every
    slot of the chunk (``score_ref`` is the chunk's ``(1, K, V)`` block)."""
    c = pl.program_id(0)
    j = pl.program_id(1)
    # the next grid step is (c, j+1), or (c+1, 0) after the last dim
    # tile; K is read as lane_rows, J·F rows of LANES per key row
    F = dblk // LANES
    slot = gather_rows(
        k_hbm, tile_rows(lambda k: colidx_ref[0, 0, k], J * F, j * F),
        tile_rows(lambda k: next_colidx_ref[0, 0, k], J * F,
                  (j + 1) % J * F),
        kbuf, sems, step=c * J + j, steps=C * J, span=F)

    def group(g, carry):
        base = pl.multiple_of(g * SUBLANES, SUBLANES)
        ktile = load_rows(kbuf, slot, base, F)   # 8 gathered key rows
        for v in range(V):
            for i in range(SUBLANES):                # stage 8 query rows
                row = lrow_ref[0, 0, base + i] * V + v
                qbuf[i:i + 1, :] = q_ref[pl.ds(row, 1), :]
            part = jnp.sum(qbuf[...] * ktile, axis=1, keepdims=True)
            sl = (0, pl.ds(base, SUBLANES), pl.ds(v, 1))
            score_ref[sl] = score_ref[sl] + part
        return carry

    jax.lax.fori_loop(0, K // SUBLANES, group, 0)


def _scratch(K: int, dblk: int, dtype):
    kbuf, sems = gather_scratch(K, dblk, dtype)
    return [kbuf, pltpu.VMEM((SUBLANES, dblk), dtype), sems]


def _chunk_specs(K: int, C: int, J: int) -> list:
    """SMEM views of ``colidx`` at this grid step's chunk and at the next
    step's (the same chunk until its last dim tile), then ``lrow``."""
    def spec(index_map):
        return pl.BlockSpec((1, 1, K), lambda c, j, *_: index_map(c, j),
                            memory_space=pltpu.SMEM)
    return [spec(lambda c, j: (c, 0, 0)),
            spec(lambda c, j: ((c + (j == J - 1)) % C, 0, 0)),
            spec(lambda c, j: (c, 0, 0))]


def _kernel(trow_ref, colidx_ref, next_colidx_ref, lrow_ref, q_ref, k_hbm,
            out_ref, kbuf, qbuf, sems, *, V: int, K: int, C: int, J: int,
            dblk: int):
    # first dim tile of this chunk → zero its score block
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    _accumulate_scores(colidx_ref, next_colidx_ref, lrow_ref, q_ref, k_hbm,
                       out_ref, kbuf, qbuf, sems, V=V, K=K, C=C, J=J,
                       dblk=dblk)


def _fused_kernel(trow_ref, init_ref,                       # scalar prefetch
                  colidx_ref, next_colidx_ref, lrow_ref, vals_ref,
                  q_ref, k_hbm,
                  score_ref, rowmax_ref, rowsum_ref,        # outputs
                  kbuf, qbuf, sems,
                  *, V: int, K: int, C: int, J: int, dblk: int, scale: float,
                  slope: float):
    c = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init_scores():
        score_ref[...] = jnp.zeros_like(score_ref)

    # first chunk of this output block → reset its softmax running stats
    @pl.when((j == 0) & (init_ref[c] == 1))
    def _init_stats():
        rowmax_ref[...] = jnp.full(rowmax_ref.shape, -jnp.inf,
                                   rowmax_ref.dtype)
        rowsum_ref[...] = jnp.zeros_like(rowsum_ref)

    _accumulate_scores(colidx_ref, next_colidx_ref, lrow_ref, q_ref, k_hbm,
                       score_ref, kbuf, qbuf, sems, V=V, K=K, C=C, J=J,
                       dblk=dblk)

    # Softmax epilogue: once the chunk's dot products are complete (last
    # dim tile), scale + LeakyReLU every slot and fold it, in slot order,
    # into the block's running row-max / row-sum-of-exp
    # (flash-attention-style online rescale).  The stats tile lives at
    # trow[c] (row stats in sublane 0, one lane per row), so split chunks
    # of one block accumulate into the same VMEM-resident tiles across
    # consecutive revisits.
    @pl.when(j == J - 1)
    def _epilogue():
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

        def group(g, stats):
            m_row, s_row = stats
            base = pl.multiple_of(g * SUBLANES, SUBLANES)
            for v in range(V):
                sl = (0, pl.ds(base, SUBLANES), pl.ds(v, 1))
                m = vals_ref[sl] != 0                 # (8, 1) real-edge mask
                x = score_ref[sl] * scale
                x = jnp.where(x >= 0, x, slope * x)   # LeakyReLU
                # masked/padding slots publish −inf: downstream
                # α = exp(logit − m)/Σ (the SpMM prologue, or the
                # backward's recompute) then comes out exactly 0 with no
                # separate mask operand; padding never drives max/sum.
                xm = jnp.where(m, x, -jnp.inf)
                score_ref[sl] = xm
                for i in range(SUBLANES):
                    row = lrow_ref[0, 0, base + i] * V + v
                    hit = lane == row
                    xi = jnp.broadcast_to(xm[i:i + 1, :], (1, LANES))
                    m_new = jnp.maximum(m_row, xi)
                    finite = jnp.isfinite(m_new)      # ≥1 real edge so far
                    s_scale = jnp.exp(jnp.where(finite, m_row - m_new, 0.0))
                    contrib = jnp.exp(jnp.where(finite, xi - m_new, -jnp.inf))
                    s_row = jnp.where(hit, s_row * s_scale + contrib, s_row)
                    m_row = jnp.where(hit, m_new, m_row)
            return m_row, s_row

        m_row, s_row = jax.lax.fori_loop(
            0, K // SUBLANES, group, (rowmax_ref[0:1, :], rowsum_ref[0:1, :]))
        rowmax_ref[0:1, :] = m_row
        rowsum_ref[0:1, :] = s_row


def sddmm_softmax_kernel(colidx, lrow, trow, init, vals, Q_padded, K_padded, *,
                         n_blocks: int, W: int, V: int, K: int, dblk: int,
                         scale: float, slope: float, interpret: bool | None = None):
    """Fused SDDMM → edge-softmax statistics, one grid pass.

    Same (C, J) traversal as ``sddmm_kernel``, plus an epilogue on each
    chunk's final dim tile that applies ``scale`` and LeakyReLU(``slope``),
    masks padding slots to −inf, and maintains per-row online-softmax
    statistics in two extra tile-aligned ``(n_blocks·SUBLANES, LANES)``
    outputs (one (8, 128) tile per block; row r of block b lives at
    ``[b·SUBLANES, r]``).  Returns ``(logits (C, V, K), rowmax, rowsum)``
    where ``rowsum`` is Σ exp(logit − rowmax) over each row's real edges —
    exactly the operands the fused ParamSpMM softmax *prologue* consumes,
    so the GAT forward needs no elementwise pass between the two kernels.
    Rows of never-visited (empty) blocks hold garbage; no real slot maps to
    them, and the prologue's −inf-logit convention keeps even padding slots
    that read garbage stats at exactly α = 0.
    """
    C = trow.shape[0]
    R = V * W
    assert R <= LANES, f"R={R} must fit one stats-tile lane row"
    assert K % SUBLANES == 0, f"K={K} must be sublane-aligned"
    dim_pad = Q_padded.shape[1]
    assert dim_pad % dblk == 0
    assert Q_padded.shape[0] % R == 0
    J = dim_pad // dblk
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(C, J),
        in_specs=_chunk_specs(K, C, J) + [
            # the chunk's slot values, slot-major (the edge mask)
            pl.BlockSpec((1, K, V), lambda c, j, tr, it: (c, 0, 0)),
            # the target block's query rows
            pl.BlockSpec((R, dblk), lambda c, j, tr, it: (tr[c], j)),
            pl.BlockSpec(memory_space=pl.ANY),        # keys stay in HBM
        ],
        out_specs=[
            pl.BlockSpec((1, K, V), lambda c, j, tr, it: (c, 0, 0)),
            pl.BlockSpec((SUBLANES, LANES), lambda c, j, tr, it: (tr[c], 0)),
            pl.BlockSpec((SUBLANES, LANES), lambda c, j, tr, it: (tr[c], 0)),
        ],
        scratch_shapes=_scratch(K, dblk, Q_padded.dtype),
    )
    name = f"sddmm_softmax_v{V}_k{K}_w{W}_d{dblk}"
    record_prefetched_share(name, C * J)
    fn = pl.pallas_call(
        functools.partial(_fused_kernel, V=V, K=K, C=C, J=J, dblk=dblk,
                          scale=scale, slope=slope),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((C, K, V), Q_padded.dtype),
            jax.ShapeDtypeStruct((n_blocks * SUBLANES, LANES), Q_padded.dtype),
            jax.ShapeDtypeStruct((n_blocks * SUBLANES, LANES), Q_padded.dtype),
        ],
        compiler_params=SEQUENTIAL,
        interpret=resolve_backend("pallas", interpret)[1],
        name=name,
    )
    colidx = colidx.reshape(C, 1, K)
    logits, rowmax, rowsum = fn(trow, init, colidx, colidx,
                                lrow.reshape(C, 1, K),
                                jnp.swapaxes(vals, 1, 2), Q_padded,
                                lane_rows(K_padded))
    return jnp.swapaxes(logits, 1, 2), rowmax, rowsum


def sddmm_kernel(colidx, lrow, trow, Q_padded, K_padded, *,
                 W: int, V: int, K: int, dblk: int,
                 interpret: bool | None = None):
    """Raw per-slot scores on pre-padded operands.

    Q_padded: (n_blocks·R, J·dblk); K_padded: (n_k, J·dblk).
    Returns scores (C, V, K) — unmasked (padding slots score garbage;
    the ops.py wrapper applies the ``vals != 0`` sampling mask).
    """
    C = trow.shape[0]
    R = V * W
    assert K % SUBLANES == 0, f"K={K} must be sublane-aligned"
    dim_pad = Q_padded.shape[1]
    assert dim_pad % dblk == 0
    assert Q_padded.shape[0] % R == 0
    J = dim_pad // dblk
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(C, J),
        in_specs=_chunk_specs(K, C, J) + [
            # the target block's query rows
            pl.BlockSpec((R, dblk), lambda c, j, tr: (tr[c], j)),
            pl.BlockSpec(memory_space=pl.ANY),        # keys stay in HBM
        ],
        out_specs=pl.BlockSpec((1, K, V), lambda c, j, tr: (c, 0, 0)),
        scratch_shapes=_scratch(K, dblk, Q_padded.dtype),
    )
    name = f"sddmm_v{V}_k{K}_w{W}_d{dblk}"
    record_prefetched_share(name, C * J)
    fn = pl.pallas_call(
        functools.partial(_kernel, V=V, K=K, C=C, J=J, dblk=dblk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((C, K, V), Q_padded.dtype),
        compiler_params=SEQUENTIAL,
        interpret=resolve_backend("pallas", interpret)[1],
        name=name,
    )
    colidx = colidx.reshape(C, 1, K)
    scores = fn(trow, colidx, colidx, lrow.reshape(C, 1, K), Q_padded,
                lane_rows(K_padded))
    return jnp.swapaxes(scores, 1, 2)
