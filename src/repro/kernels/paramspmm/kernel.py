"""ParamSpMM Pallas TPU kernel (paper Alg. 2, TPU adaptation per DESIGN.md §2)
with fused prologue / epilogue.

Grid ``(J, C)`` = (dim-tiles, chunks).  Each grid step owns one chunk of
``K`` slots:

1. the chunk's steering — ``colidx``/``lrow`` slices and its ``(V, K)``
   values — arrives in SMEM as ordinary pipelined blocks, so only the
   per-chunk ``trow``/``init``/``fini`` vectors are scalar-prefetched
   whole (whole-array prefetch of the per-slot arrays overflows the
   1 MiB SMEM of a v5e at ~130k slots);
2. the chunk's ``K`` rows of ``B`` (one ``Dblk``-lane tile each) are
   gathered HBM → VMEM with manual async copies — the TPU replacement
   for the CUDA warp's irregular global load — by ``gather_rows``, a
   two-slot pipeline across grid steps: each step issues the *next*
   step's rows into the other slot (their ids from a second SMEM view of
   ``colidx``, one step ahead) before it waits, once, for its own slot,
   so the next chunk's copies fly while this chunk computes.  ``B`` is
   read as ``lane_rows(B)``, one 128-lane row per lane tile, so a
   gathered row of ``F`` lane tiles is one copy of ``F`` whole rows;
3. the slots are consumed in order, eight gathered rows per
   ``(8, Dblk)`` tile (``load_rows``: one aligned load at ``F = 1``, ``F``
   sublane-strided loads beyond), each scaled by its slot value and
   added into the ``V`` output rows of its panel.

The ``(R, Dblk)`` output block is revisited across consecutive chunks
with the same ``trow`` and accumulated in VMEM: with ``S=True`` several
chunks target one block (the paper's ``TRow`` + ``atomicAdd``, made
race-free by the sequential grid; both grid axes are ``"arbitrary"``,
since the gather's slots also carry state from one step to the next).

Parameter mapping (paper → here):
  V → rows fed per gathered B row (slot values ``(V, K)`` per chunk);
  F → ``Dblk = F·128`` lanes per gathered tile (thread coarsening);
  W → ``R = V·W`` output-block rows;
  S → chunking policy baked into the PCSR arrays (kernel is agnostic).

Fusion:

* **Softmax prologue** (``prologue=True``): ``vals`` carries raw attention
  *logits* (masked slots = −inf) and two extra tile-aligned
  ``(n_blocks·SUBLANES, LANES)`` inputs — one (8, 128) tile per block,
  row stats in sublane 0 — carry the per-row online-softmax stats the
  fused SDDMM produced (its native output layout; the block's tile is
  read from SMEM).  The attention weight α = exp(logit − rowmax)/rowsum
  is computed in-register while the gathered B row is consumed — the
  normalize pass between SDDMM and SpMM disappears, making the GAT
  forward exactly TWO kernels.
* **Packed heads** (``heads`` = H > 1): the operand holds H attention
  heads side by side, head h in lanes ``h·d … h·d+d−1`` of one row, and
  the grid is one head's chunks, so one gathered row serves every head.
  Per-head slot values ``(C, V, K, H)`` arrive as a VMEM block and are
  spread across lanes (``spread_heads``); the prologue's stats are
  per-row lane vectors ``(n_blocks·R, Dblk)`` read as an ``(R, Dblk)``
  block.  Stored values are every head's, broadcast across lanes.
* **Epilogue** (``scale``/``bias``/``residual``/``activation``): on the
  last visit of each output block — ``fini[c] == 1``, the moment the
  completed ``(R, Dblk)`` tile is still VMEM-resident — a per-row
  degree-norm scale, per-feature bias add, dense residual add (the
  matching ``(R, Dblk)`` tile of a full ``(n, d)`` operand — GIN's
  ``(1+ε)h`` term), and activation are applied before write-back, so a
  GCN aggregation step (and a GIN ``(1+ε)h + A·h`` aggregation) is ONE
  kernel instead of kernel + 2–3 XLA elementwise passes.

Padding-slot safety under the prologue: a masked/padding slot carries
logit = −inf, so exp(−inf − m) = 0 regardless of the row stats — even the
garbage stats of never-visited rows (the ``isfinite``/``> 0`` guards keep
the 0 exact instead of NaN).  Coverage chunks for empty blocks (see
``PCSR.steering(covered=True)``) therefore accumulate exactly zero.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.pcsr import LANES, SUBLANES
from repro.kernels import resolve_backend
from repro.obs import metrics as _obs_metrics

ACTIVATIONS = ("none", "relu", "leaky_relu")


def lane_rows(X):
    """``X`` of shape ``(n, T·LANES)`` as the ``(n·T, LANES)`` array the
    gather copies from: lane tile ``t`` of row ``r`` becomes row
    ``r·T + t``, so a gathered row of ``F`` lane tiles is ``F`` whole
    consecutive rows (the v5e's DMA takes no one-row slice of an HBM
    array wider than one lane tile)."""
    return X if X.shape[1] == LANES else X.reshape(-1, LANES)


def tile_rows(ids, tiles: int, first):
    """Row ids of ``lane_rows`` for a gather of dim tiles: row ``ids(k)``
    of an operand of ``tiles`` lane tiles, from its lane tile ``first``
    on.  One lane tile keeps the ids as they are."""
    if tiles == 1:
        return ids
    return lambda k: ids(k) * tiles + first


def gather_rows(src_hbm, rows_now, rows_next, buf, sems, *, step, steps,
                span: int):
    """Two-slot row gather, pipelined across a sequential grid.

    ``src_hbm`` is a ``lane_rows`` operand ``(m, LANES)``; ``buf`` is
    ``(2, K·span, LANES)`` VMEM (``K`` a multiple of ``SUBLANES``) with one
    DMA semaphore per slot in ``sems``; grid step ``step`` of ``steps``
    owns slot ``step % 2``.  Gathered row ``k`` of this step's chunk is
    the ``span`` source rows from ``rows_now(k)`` on (``Dblk = span·LANES``
    lanes), of the next step's those from ``rows_next(k)``; each lands in
    ``buf`` rows ``k·span …`` by one async copy (HBM → VMEM).  Step 0
    issues its own rows; every step but the last issues the next step's
    rows into the other slot, then waits for its own slot — so the next
    chunk's copies are in flight while this one computes.  Returns this
    step's slot; ``load_rows`` reads it."""
    K = buf.shape[1] // span
    slot = step % 2

    def issue(rows, s):
        def start(g, carry):
            # eight copies per iteration: the scalar loop's own overhead
            # is paid once per eight rows, not once per row
            for i in range(SUBLANES):
                k = g * SUBLANES + i
                # (no multiply in the loop at one lane tile)
                pltpu.make_async_copy(
                    src_hbm.at[pl.ds(rows(k), span)],
                    buf.at[s, pl.ds(k * span if span > 1 else k, span)],
                    sems.at[s]).start()
            return carry
        jax.lax.fori_loop(0, K // SUBLANES, start, 0)

    @pl.when(step == 0)
    def _prime():
        issue(rows_now, 0)

    @pl.when(step + 1 < steps)
    def _prefetch():
        issue(rows_next, 1 - slot)

    # one wait for the whole slot: its K row copies signal one semaphore
    # with their bytes, and a descriptor the slot's size waits for all
    pltpu.make_async_copy(buf.at[slot], buf.at[slot], sems.at[slot]).wait()
    return slot


def load_rows(buf, slot, base, span: int):
    """The ``(SUBLANES, span·LANES)`` tile of gathered rows ``base …
    base+7`` (``base`` a multiple of ``SUBLANES``): lane tile ``f`` is a
    sublane-strided load of ``buf`` rows ``(base+i)·span + f``."""
    if span == 1:
        return buf[slot, pl.ds(base, SUBLANES), :]
    return jnp.concatenate(
        [buf[slot, pl.ds(base * span + f, SUBLANES, stride=span), :]
         for f in range(span)], axis=1)


def gather_scratch(K: int, dblk: int, dtype) -> list:
    """Scratch of ``gather_rows``: the two-slot buffer and its semaphores."""
    return [pltpu.VMEM((2, K * (dblk // LANES), LANES), dtype),
            pltpu.SemaphoreType.DMA((2,))]


def spread_heads(x, width: int, head_dim: int):
    """``x`` (rows, H) as (rows, ``width``) lane vectors: lane l holds
    head ``l // head_dim``'s entry, and lanes past H·``head_dim`` hold 0
    — one select per head over a lane iota."""
    head = jax.lax.broadcasted_iota(jnp.int32, (x.shape[0], width),
                                    1) // head_dim
    out = jnp.zeros((x.shape[0], width), x.dtype)
    for h in range(x.shape[1]):
        out = jnp.where(head == h, x[:, h:h + 1], out)
    return out


def record_prefetched_share(name: str, steps: int) -> None:
    """Gauge ``kernel_gather_prefetched_share{kernel=name}``: the share of
    a call's grid steps whose rows were in flight before the step began
    (all but step 0), set when the call is traced."""
    _obs_metrics.gauge("kernel_gather_prefetched_share").set(
        (steps - 1) / steps, kernel=name)


def record_gather_lanes(name: str, *, C: int, J: int, K: int, dblk: int,
                        width: int) -> None:
    """Counter ``kernel_gather_lanes_total{kernel=name, lanes}``, added to
    when a call is traced: the lanes its C·J grid steps gather,
    C·J·K·``dblk`` (``moved``), and those that carry one of the
    operand's ``width`` columns, C·K·``width`` (``carried``)."""
    lanes = _obs_metrics.counter("kernel_gather_lanes_total")
    lanes.inc(C * J * K * dblk, kernel=name, lanes="moved")
    lanes.inc(C * K * width, kernel=name, lanes="carried")


SEQUENTIAL = pltpu.CompilerParams(
    dimension_semantics=("arbitrary", "arbitrary"))
"""Both grid axes run in order: the gather's slots carry state across
steps, and output blocks are revisited by consecutive steps."""


def _kernel(trow_ref, init_ref, fini_ref,                   # scalar prefetch
            colidx_ref, next_colidx_ref, lrow_ref, vals_ref, b_hbm, *refs,
            V: int, K: int, C: int, J: int, dblk: int, prologue: bool,
            head_dim: int | None, has_scale: bool, has_bias: bool,
            has_resid: bool, activation: str, slope: float):
    j = pl.program_id(0)
    c = pl.program_id(1)

    it = iter(refs)
    rowmax_ref = next(it) if prologue else None
    rowsum_ref = next(it) if prologue else None
    scale_ref = next(it) if has_scale else None
    bias_ref = next(it) if has_bias else None
    resid_ref = next(it) if has_resid else None
    out_ref, gbuf, sems = next(it), next(it), next(it)

    # First visit of this output block in this dim-tile pass → zero it.
    @pl.when(init_ref[c] == 1)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    # the next grid step is (j, c+1), or (j+1, 0) after the last chunk;
    # B is read as lane_rows, J·F rows of LANES per row of B
    F = dblk // LANES
    slot = gather_rows(
        b_hbm,
        tile_rows(lambda k: colidx_ref[0, 0, k], J * F, j * F),
        tile_rows(lambda k: next_colidx_ref[0, 0, k], J * F,
                  (j + (c == C - 1)) % J * F),
        gbuf, sems, step=j * C + c, steps=J * C, span=F)

    spread = head_dim and len(vals_ref.shape) == 4   # per-head values

    def group(g, carry):
        base = pl.multiple_of(g * SUBLANES, SUBLANES)
        tile = load_rows(gbuf, slot, base, F)     # 8 gathered B rows
        if spread:                                # their lane vectors
            lane_vals = [spread_heads(vals_ref[0, v, pl.ds(base, SUBLANES)],
                                      dblk, head_dim) for v in range(V)]
        for i in range(SUBLANES):
            k = base + i
            row = lrow_ref[0, 0, k] * V           # panel's first row
            brow = tile[i:i + 1, :]               # (1, Dblk)
            for v in range(V):
                if spread:
                    vv = lane_vals[v][i:i + 1, :]
                else:
                    vv = jnp.full((1, 1), vals_ref[0, v, k], brow.dtype)
                if prologue:
                    # α = exp(logit − rowmax)/rowsum in-register.  Guards:
                    # empty rows have rowmax = −inf / rowsum = 0 (or
                    # garbage when the row's block was never visited by
                    # the SDDMM); masked and padding slots have logit
                    # = −inf, so α must come out exactly 0.
                    if head_dim:                  # the row's stats per lane
                        m = rowmax_ref[pl.ds(row + v, 1), :]
                        s = rowsum_ref[pl.ds(row + v, 1), :]
                    else:
                        m = jnp.full((1, 1), rowmax_ref[0, row + v],
                                     brow.dtype)
                        s = jnp.full((1, 1), rowsum_ref[0, row + v],
                                     brow.dtype)
                    m = jnp.where(jnp.isfinite(m), m, 0.0)
                    den = jnp.where(s > 0, s, 1.0)
                    vv = jnp.exp(vv - m) / den
                r = pl.ds(row + v, 1)
                out_ref[r, :] = out_ref[r, :] + vv * brow
        return carry

    jax.lax.fori_loop(0, K // SUBLANES, group, 0)

    if has_scale or has_bias or has_resid or activation != "none":
        # Last visit of this output block → the accumulated (R, Dblk)
        # tile is complete for this dim tile; apply the fused epilogue
        # while it is still VMEM-resident.
        @pl.when(fini_ref[c] == 1)
        def _epilogue():
            y = out_ref[...]
            if has_scale:
                y = y * scale_ref[...].astype(y.dtype)   # (R, 1) per row
            if has_bias:
                y = y + bias_ref[0:1, :].astype(y.dtype)
            if has_resid:
                # the residual operand's matching (R, Dblk) tile
                y = y + resid_ref[...].astype(y.dtype)
            if activation == "relu":
                y = jnp.maximum(y, 0.0)
            elif activation == "leaky_relu":
                y = jnp.where(y >= 0, y, slope * y)
            out_ref[...] = y


def paramspmm_kernel(colidx, lrow, trow, init, fini, vals, B_padded, *,
                     n_blocks: int, R: int, V: int, K: int, dblk: int,
                     rowmax=None, rowsum=None, scale=None, bias=None,
                     residual=None, activation: str = "none",
                     slope: float = 0.2, width: int | None = None,
                     widest: int | None = None, heads: int = 1,
                     interpret: bool | None = None):
    """Invoke the Pallas kernel on pre-padded operands.

    B_padded: (n_b, J·dblk), of which the first ``width`` columns (all by
    default) carry data.  Returns C_padded (n_blocks·R, J·dblk).
    ``widest`` is the picked config's dim tile where the caller narrowed
    ``dblk`` from it (``SpMMConfig.dblk_for``); a narrower ``dblk`` is
    counted in ``spmm_dblk_narrowed_total{kernel}``.

    ``heads`` = H > 1 packs H attention heads into the ``width`` data
    lanes of one dim tile (head h in lanes ``h·d … h·d+d−1``, d =
    ``width // H``); the call is counted in
    ``spmm_heads_packed_total{kernel}``.  ``vals`` is then (C, V, K),
    every head's, or (C, V, K, H), per head.

    Optional fusion operands:
      rowmax/rowsum (n_blocks·SUBLANES, LANES) — softmax prologue stats
                      (vals = logits) in the fused SDDMM's native layout:
                      one (8, 128) tile per block, row r of block b at
                      ``[b·SUBLANES, r]``; with packed heads
                      (n_blocks·R, dblk), row r's stat of head h in its
                      lanes ``h·d … h·d+d−1``;
      scale (n_blocks·R, 1) — per-row epilogue scale (degree norm),
                      packed by ``ops._pack_scale``;
      bias (SUBLANES, J·dblk) — per-feature epilogue bias (row 0 real);
      residual (n_blocks·R, J·dblk) — dense epilogue addend in the
                      output's own padded block layout; each output
                      block's last visit adds its matching (R, Dblk)
                      tile (GIN's ``(1+ε)h`` term);
      activation    — "none" | "relu" | "leaky_relu" epilogue.
    """
    if activation not in ACTIVATIONS:
        raise ValueError(f"activation {activation!r} not in {ACTIVATIONS}")
    assert R <= LANES, f"R={R} must fit one stats-tile lane row"
    assert K % SUBLANES == 0, f"K={K} must be sublane-aligned"
    C = trow.shape[0]
    dim_pad = B_padded.shape[1]
    assert dim_pad % dblk == 0
    head_dim = None
    stats_shape = (n_blocks * SUBLANES, LANES)
    if heads > 1:
        assert width is not None and width % heads == 0 and dim_pad == dblk, (
            f"{heads} heads must split the {width} data lanes of one "
            f"{dblk}-lane tile")
        head_dim, stats_shape = width // heads, (n_blocks * R, dblk)
    assert vals.ndim == 3 or heads > 1 and vals.shape == (C, V, K, heads), (
        f"per-head vals must be ({C}, {V}, {K}, {heads}), got {vals.shape}")
    for name, arr in (("rowmax", rowmax), ("rowsum", rowsum)):
        assert arr is None or arr.shape == stats_shape, (
            f"{name} must be {stats_shape}, got {arr.shape}")
    assert scale is None or scale.shape == (n_blocks * R, 1), (
        f"scale must be ({n_blocks * R}, 1), got {scale.shape}")
    assert bias is None or bias.shape == (SUBLANES, dim_pad), (
        f"bias must be ({SUBLANES}, {dim_pad}), got {bias.shape}")
    J = dim_pad // dblk
    prologue = rowmax is not None
    smem = pltpu.SMEM

    def chunk_spec(rows):
        return pl.BlockSpec((1, rows, K), lambda j, c, tr, it, fi: (c, 0, 0),
                            memory_space=smem)

    # the next grid step's row ids: a second view of colidx, one chunk on
    next_ids = pl.BlockSpec((1, 1, K),
                            lambda j, c, tr, it, fi: ((c + 1) % C, 0, 0),
                            memory_space=smem)
    # per-head values: the chunk's (V, K, H) block, in VMEM
    vals_spec = (chunk_spec(V) if vals.ndim == 3 else pl.BlockSpec(
        (1, V, K, heads), lambda j, c, tr, it, fi: (c, 0, 0, 0)))
    in_specs = [chunk_spec(1), next_ids, chunk_spec(1), vals_spec,
                pl.BlockSpec(memory_space=pl.ANY)]          # B stays in HBM
    colidx = colidx.reshape(C, 1, K)
    operands = [colidx, colidx, lrow.reshape(C, 1, K), vals,
                lane_rows(B_padded)]
    if prologue:
        # the block's stats tile in SMEM, or with packed heads its (R,
        # Dblk) lane vectors in VMEM
        stats_spec = (pl.BlockSpec((R, dblk),
                                   lambda j, c, tr, it, fi: (tr[c], 0))
                      if head_dim else
                      pl.BlockSpec((SUBLANES, LANES),
                                   lambda j, c, tr, it, fi: (tr[c], 0),
                                   memory_space=smem))
        in_specs += [stats_spec, stats_spec]
        operands += [rowmax, rowsum]
    if scale is not None:
        in_specs.append(pl.BlockSpec(
            (R, 1), lambda j, c, tr, it, fi: (tr[c], 0)))
        operands.append(scale)
    if bias is not None:
        in_specs.append(pl.BlockSpec(
            (SUBLANES, dblk), lambda j, c, tr, it, fi: (0, j)))
        operands.append(bias)
    if residual is not None:
        assert residual.shape == (n_blocks * R, dim_pad), (
            f"residual must match the padded output "
            f"({n_blocks * R}, {dim_pad}), got {residual.shape}")
        in_specs.append(pl.BlockSpec(
            (R, dblk), lambda j, c, tr, it, fi: (tr[c], j)))
        operands.append(residual)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(J, C),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((R, dblk),
                               lambda j, c, tr, it, fi: (tr[c], j)),
        scratch_shapes=gather_scratch(K, dblk, B_padded.dtype),
    )
    name = (f"paramspmm_v{V}_k{K}_r{R}_d{dblk}"
            f"{'_pro' if prologue else ''}"
            f"{'_res' if residual is not None else ''}"
            f"{'' if activation == 'none' else '_' + activation}"
            f"{f'_h{heads}' if heads > 1 else ''}")
    record_prefetched_share(name, C * J)
    record_gather_lanes(name, C=C, J=J, K=K, dblk=dblk,
                        width=dim_pad if width is None else width)
    if widest is not None and dblk < widest:
        _obs_metrics.counter("spmm_dblk_narrowed_total").inc(kernel=name)
    if heads > 1:
        _obs_metrics.counter("spmm_heads_packed_total").inc(kernel=name)
    fn = pl.pallas_call(
        functools.partial(_kernel, V=V, K=K, C=C, J=J, dblk=dblk,
                          prologue=prologue, head_dim=head_dim,
                          has_scale=scale is not None,
                          has_bias=bias is not None,
                          has_resid=residual is not None,
                          activation=activation, slope=slope),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_blocks * R, dim_pad), B_padded.dtype),
        compiler_params=SEQUENTIAL,
        interpret=resolve_backend("pallas", interpret)[1],
        name=name,
    )
    return fn(trow, init, fini, *operands)
