"""Parameterized Compressed Sparse Row (PCSR) — TPU adaptation.

The paper's PCSR stores ``rowPtr/colIdx/val/TRow`` parameterized by
⟨W, F, V, S⟩ (Section 4.2).  On TPU the format is re-derived for a
sequential Pallas grid (see DESIGN.md §2):

* nonzeros are grouped into ``V×1`` column-vectors inside V-row *panels*
  (vectorized blocking — one gathered row of ``B`` feeds V output rows);
* ``W`` panels form an output *block* of ``R = V·W`` rows (the unit the
  kernel accumulates in VMEM);
* each block's vectors are packed into fixed-capacity *chunks* of ``K``
  slots.  ``S=False`` → row-aligned chunks with capacity ≈ the maximum
  block population (the static-grid analogue of "one warp per row");
  ``S=True`` → capacity ``K = SG`` derived from the mean population
  (the paper's Split Granularity, Eq. 3, with warp-size roundup replaced
  by sublane roundup), so heavy blocks split across several chunks that
  the kernel accumulates via consecutive output-block revisits (the
  TPU analogue of the paper's ``TRow`` + ``atomicAdd``);
* ``B=True`` (requires ``S=True``) → the *nnz-balanced* schedule: the
  capacity comes from ``balanced_capacity`` (a search over the block
  population *distribution*, not just its mean), each block's vectors
  are round-robined across its chunks so per-chunk nnz is near-uniform
  (no mostly-empty tail chunk), and chunks are emitted in LPT order
  (descending block population, each block's chunks contiguous — the
  ``fini``/VMEM-revisit machinery only needs *grouped* ``trow``, not
  ascending).  On a power-law graph this removes most of the padding
  slots the mean-derived ``SG`` wastes on the long tail of light
  blocks — the total-slot count is the sequential grid's makespan.

Everything here is host-side preprocessing in vectorized numpy — the
paper performs PCSR generation on the host as well, amortized across
training iterations.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from time import perf_counter

import numpy as np

from repro.obs import metrics as _obs_metrics, trace as _obs_trace

LANES = 128          # TPU lane width (the paper's warp size ω=32 analogue)
SUBLANES = 8         # f32 sublane quantum

# Memory guard for the unbalanced mode: a power-law max-degree block would
# otherwise pad *every* chunk to the global max.  Capping keeps host memory
# bounded while preserving the skew penalty the paper attributes to S=False.
UNBALANCED_CAP = 8192


def _round_up(x: int, m: int) -> int:
    return -(-int(x) // m) * m


@dataclass(frozen=True)
class SpMMConfig:
    """The paper's ⟨W, F, V, S⟩ tuple, plus the TPU ``B`` (balanced) axis.

    V: vector size of blocking (paper domain {1, 2}).
    S: workload balancing on/off.
    F: coarsening factor — the widest dim tile the pick allows,
       ``Dblk = F·128`` lanes.  Each call narrows it to its operand
       (``dblk_for``): the packed steering arrays do not depend on F.
    W: panels per output block — block height ``R = V·W`` rows.
    B: nnz-balanced chunk schedule (distribution-derived capacity +
       round-robin slot packing + LPT chunk order).  Requires ``S=True``
       — balancing is a refinement of the split-chunk layout; the kernel
       is unchanged, only the steering arrays differ.
    """

    V: int = 1
    S: bool = False
    F: int = 1
    W: int = 8
    B: bool = False

    def __post_init__(self):
        if self.V < 1 or self.F < 1 or self.W < 1:
            raise ValueError(f"invalid config {self}")
        if self.B and not self.S:
            raise ValueError(f"B=True requires S=True ({self})")

    @property
    def R(self) -> int:
        return self.V * self.W

    @property
    def dblk(self) -> int:
        return self.F * LANES

    def dblk_for(self, width: int) -> int:
        """The lane tile a call on a ``width``-wide operand gathers: the
        narrowest whole tile that holds the row when ``dblk`` would (one
        dim tile, padded lanes cost bytes and buy nothing), else ``dblk``
        (wider operands keep the pick's dim tiling)."""
        if width <= self.dblk:
            return _round_up(width, LANES)
        return self.dblk

    def astuple(self):
        return (self.W, self.F, self.V, self.S, self.B)

    def replace(self, **kw) -> "SpMMConfig":
        return dataclasses.replace(self, **kw)


def config_space(dim: int, max_f: int = 4):
    """Enumerate the search domain for a given embedding dim.

    V ∈ {1,2} (paper limits V to {1,2}: V=3 pads >50% on 97.5% of graphs);
    S ∈ {False,True}; F ∈ [1, CEIL(dim/128)] (the paper's
    F ∈ [1, CEIL(dim/ω)] with ω=128 on TPU); R = V·W ∈ {8,16,32}.

    Balanced (``B=True``, implies ``S=True``) variants are appended AFTER
    the uniform configs so an exact price tie — the degenerate case on
    uniform-degree graphs, where ``balanced_capacity`` lands on the same
    ``K`` as the mean-derived SG — resolves to the uniform layout under
    ``CostModel.best``'s strict ``<``.
    """
    fs = list(range(1, min(max_f, _round_up(dim, LANES) // LANES) + 1))
    out = []
    for v in (1, 2):
        for s in (False, True):
            for f in fs:
                for r in (8, 16, 32):
                    out.append(SpMMConfig(V=v, S=s, F=f, W=r // v))
    for v in (1, 2):
        for f in fs:
            for r in (8, 16, 32):
                out.append(SpMMConfig(V=v, S=True, F=f, W=r // v, B=True))
    return out


@dataclass
class PCSR:
    """Packed PCSR arrays (numpy, host-resident) + bookkeeping stats."""

    config: SpMMConfig
    n_rows: int            # rows of A (= rows of C)
    n_cols: int            # cols of A (= rows of B)
    n_blocks: int          # output blocks of R rows each
    K: int                 # chunk capacity (slots)
    colidx: np.ndarray     # (C·K,) int32 — B-row per slot (pad → 0)
    lrow: np.ndarray       # (C·K,) int32 — panel idx within block
    trow: np.ndarray       # (C,)   int32 — target block per chunk
    init: np.ndarray       # (C,)   int32 — 1 iff first chunk of its block
    vals: np.ndarray       # (C,V,K) float32 — vector values (pad → 0)
    nnz: int
    nnz_vec: int           # number of nonzero vectors
    n_nonempty_blocks: int

    @property
    def num_chunks(self) -> int:
        return int(self.trow.shape[0])

    @property
    def num_slots(self) -> int:
        return self.num_chunks * self.K

    @property
    def padding_ratio(self) -> float:
        """PR_V (paper Eq. 2): 1 - nnz / (nnz_V · V)."""
        if self.nnz_vec == 0:
            return 0.0
        return 1.0 - self.nnz / (self.nnz_vec * self.config.V)

    @property
    def split_ratio(self) -> float:
        """SR (paper Eq. 4): reassigned-rowPtr length over original."""
        return self.num_chunks / max(1, self.n_nonempty_blocks)

    @property
    def slot_fill(self) -> float:
        """Fraction of chunk slots holding a real vector."""
        return self.nnz_vec / max(1, self.num_slots)

    def nbytes(self) -> int:
        return (self.colidx.nbytes + self.lrow.nbytes + self.trow.nbytes
                + self.init.nbytes + self.vals.nbytes)

    def to_jax(self):
        """Device-ready uncovered H=1 arrays, routed through the
        ``steering()`` cache so every backend shares one pack accessor
        (and its hit/miss accounting)."""
        import jax.numpy as jnp
        st = self.steering()
        return {k: jnp.asarray(st[k])
                for k in ("colidx", "lrow", "trow", "init", "vals")}

    @property
    def fini(self) -> np.ndarray:
        """(C,) int32 — 1 iff the chunk is the LAST chunk of its block.

        The mirror of ``init``: where ``init`` steers the kernel's
        zero-on-first-visit, ``fini`` steers the fused *epilogue* — the
        last ``(j, k)`` step of a block is the one moment the completed
        ``(R, Dblk)`` output tile is still VMEM-resident, so scale/bias/
        activation can be applied for free before write-back.  ``trow`` is
        *grouped* by construction — each block's chunks are contiguous
        (ascending in the uniform modes, LPT order under ``B=True``) — so
        the last chunk of each block is the one whose successor targets a
        different block.
        """
        f = self.__dict__.get("_fini")
        if f is None:
            f = np.ones(self.num_chunks, np.int32)
            f[:-1] = (self.trow[1:] != self.trow[:-1]).astype(np.int32)
            self.__dict__["_fini"] = f
        return f

    @property
    def n_empty_blocks(self) -> int:
        """Blocks no chunk targets (their coverage chunks — see
        ``steering(covered=True)`` — are all-padding)."""
        return self.n_blocks - len(np.unique(self.trow))

    @property
    def covered_num_chunks(self) -> int:
        """Per-head chunk count of the *covered* steering arrays
        (``num_chunks`` real chunks + one all-padding coverage chunk per
        empty block).  The distributed branches slice the mesh-packed
        covered arrays with this; the per-head layout puts the real
        chunks first (prefix property), so ``[:num_chunks]`` of each
        head's segment recovers the uncovered arrays."""
        return self.num_chunks + self.n_empty_blocks

    def steering(self, H: int = 1, covered: bool = False):
        """Steering arrays for the kernels (cached per (H, covered)).

        ``H > 1`` tiles the chunk list for an H-head batch: ``colidx`` is
        offset by ``h·n_cols`` (heads stacked along the gather source's row
        axis) and ``trow`` by ``h·n_blocks`` (heads stacked along the
        output's block axis), so ONE kernel call — and one compilation —
        covers every head instead of a per-head ``vmap``.

        ``covered=True`` appends one all-padding chunk per *empty* block
        (``init = fini = 1``, ``vals = 0``) so the sequential grid visits —
        and therefore zero-initializes — every output block.  This folds
        the unvisited-block zeroing into the kernel's own ``init`` path:
        no post-kernel O(n_blocks·R·dim) elementwise mask pass remains,
        and the fused epilogue (bias on empty rows!) applies uniformly.
        The appended chunks come LAST, so the first ``C·K`` entries of a
        covered array are exactly the uncovered ones (prefix property the
        distributed packing relies on).
        """
        cache = self.__dict__.setdefault("_steering_cache", {})
        key = (H, covered)
        if key in cache:
            _obs_metrics.counter("pack_cache_hits_total").inc(
                H=H, covered=covered)
            return cache[key]
        _obs_metrics.counter("pack_cache_misses_total").inc(
            H=H, covered=covered)
        colidx, lrow = self.colidx, self.lrow
        trow, init, fini, vals = self.trow, self.init, self.fini, self.vals
        if covered:
            empty = np.setdiff1d(np.arange(self.n_blocks, dtype=np.int64),
                                 trow.astype(np.int64))
            E = len(empty)
            if E:
                colidx = np.concatenate([colidx, np.zeros(E * self.K, np.int32)])
                lrow = np.concatenate([lrow, np.zeros(E * self.K, np.int32)])
                trow = np.concatenate([trow, empty.astype(np.int32)])
                init = np.concatenate([init, np.ones(E, np.int32)])
                fini = np.concatenate([fini, np.ones(E, np.int32)])
                vals = np.concatenate(
                    [vals, np.zeros((E, self.config.V, self.K), np.float32)])
        if H > 1:
            hh = np.arange(H, dtype=np.int64)
            colidx = (np.tile(colidx, (H, 1))
                      + (hh * self.n_cols)[:, None]).reshape(-1).astype(np.int32)
            trow = (np.tile(trow, (H, 1))
                    + (hh * self.n_blocks)[:, None]).reshape(-1).astype(np.int32)
            lrow, init, fini = (np.tile(a, H) for a in (lrow, init, fini))
            vals = np.tile(vals, (H, 1, 1))
        cache[key] = {"colidx": colidx, "lrow": lrow, "trow": trow,
                      "init": init, "fini": fini, "vals": vals}
        return cache[key]


def _vectorize(indptr, indices, data, n_rows, n_cols, V):
    """Group nonzeros into V×1 panel vectors.

    Returns (vec_panel, vec_col, vec_val[nv, V]) sorted by (panel, col).
    """
    nnz = int(indices.shape[0])
    if nnz == 0:
        return (np.zeros(0, np.int64), np.zeros(0, np.int64),
                np.zeros((0, V), np.float32))
    rows = np.repeat(np.arange(n_rows, dtype=np.int64), np.diff(indptr))
    panel = rows // V
    off = (rows - panel * V).astype(np.int64)
    key = panel * n_cols + indices.astype(np.int64)
    ukey, inv = np.unique(key, return_inverse=True)
    vec_val = np.zeros((ukey.shape[0], V), np.float32)
    # canonical CSR has unique (row, col); direct assignment is exact.
    vec_val[inv, off] = data.astype(np.float32)
    return ukey // n_cols, ukey % n_cols, vec_val


def split_granularity(nnz_vec: int, n_nonempty_blocks: int) -> int:
    """Paper Eq. 3: SG = CEILDIV(d̂_V, ω)·ω, sublane-aligned on TPU."""
    mean = -(-max(1, nnz_vec) // max(1, n_nonempty_blocks))
    return max(SUBLANES, _round_up(mean, SUBLANES))


# Chunks a balanced schedule is willing to add per removed slot-octet: the
# capacity search charges each extra chunk as ``BALANCE_LAMBDA`` padding
# slots, mirroring the cost model's per-chunk ``CHUNK_SETUP`` overhead
# (steering fetch + vals DMA issue) so the packer and the pricing agree on
# when splitting finer stops paying.
BALANCE_LAMBDA = 4.0


def balanced_capacity(counts, lam: float = BALANCE_LAMBDA,
                      unbalanced_cap: int = UNBALANCED_CAP) -> int:
    """Chunk capacity minimizing ``slots(K) + lam · chunks(K)`` over the
    block-population *distribution* (the mean-derived SG of Eq. 3 only
    sees its first moment).

    ``slots(K) = Σ_b ceil(cnt_b/K)·K`` is the sequential grid's makespan
    (every slot is one grid step, padding included); ``chunks(K)`` prices
    per-chunk setup.  Candidates are the sublane roundups of the
    population quantiles + mean — O(1) evaluations of an O(n_blocks)
    objective, deterministic, and within a sublane of the true optimum on
    every corpus family (the objective is piecewise-linear between
    population order statistics).
    """
    counts = np.asarray(counts, np.int64)
    counts = counts[counts > 0]
    if counts.size == 0:
        return SUBLANES
    qs = np.quantile(counts, [0.25, 0.5, 0.75, 0.9, 0.95, 0.99, 1.0])
    cand = {max(SUBLANES, min(_round_up(int(q), SUBLANES),
                              _round_up(unbalanced_cap, SUBLANES)))
            for q in np.concatenate([qs, [counts.mean()]])}
    best_k, best_obj = SUBLANES, np.inf
    for K in sorted(cand):
        nch = -(-counts // K)
        C = int(nch.sum())
        obj = C * K + lam * C
        if obj < best_obj:
            best_k, best_obj = K, obj
    return best_k


def build_pcsr(indptr, indices, data, n_rows, n_cols,
               config: SpMMConfig, unbalanced_cap: int = UNBALANCED_CAP,
               capacity: int | None = None) -> PCSR:
    """PCSR generation (paper §4.2), fully vectorized.

    ``config.B`` selects the nnz-balanced packer: capacity from
    ``balanced_capacity``, each block's vectors round-robined across its
    chunks (per-chunk nnz within a block differs by ≤ 1 — a fat row's
    vectors split evenly over every chunk instead of filling chunks
    left-to-right and leaving a mostly-padding tail), chunks emitted in
    LPT (descending block population) order with each block's chunks
    contiguous.  Downstream machinery only relies on *grouped* ``trow``
    (``fini``/consecutive-revisit accumulation), never on ascending
    order, so the schedule needs no kernel change.

    ``capacity`` pins the chunk capacity ``K`` (sublane-rounded) instead
    of deriving it from the matrix — the serving tier uses this so every
    graph packed into one shape bucket shares the bucket's fixed chunk
    geometry (and therefore one compiled kernel).
    """
    if not _obs_trace.trace_enabled():
        return _build_pcsr(indptr, indices, data, n_rows, n_cols,
                           config, unbalanced_cap, capacity)
    with _obs_trace.span("pcsr.build", config=str(config.astuple()),
                         n_rows=int(n_rows),
                         nnz=int(np.asarray(indices).shape[0])):
        t0 = perf_counter()
        p = _build_pcsr(indptr, indices, data, n_rows, n_cols,
                        config, unbalanced_cap, capacity)
        _obs_metrics.histogram("pack_build_seconds").observe(
            perf_counter() - t0, config=str(config.astuple()))
    return p


def _build_pcsr(indptr, indices, data, n_rows, n_cols,
                config: SpMMConfig, unbalanced_cap: int,
                capacity: int | None = None) -> PCSR:
    V, W, S, Bal = config.V, config.W, config.S, config.B
    indptr = np.asarray(indptr, np.int64)
    indices = np.asarray(indices, np.int64)
    data = np.asarray(data)
    nnz = int(indices.shape[0])
    n_panels = max(1, _round_up(n_rows, V) // V)
    n_blocks = max(1, _round_up(n_panels, W) // W)

    vec_panel, vec_col, vec_val = _vectorize(indptr, indices, data,
                                             n_rows, n_cols, V)
    nv = int(vec_panel.shape[0])
    bid = vec_panel // W                      # block of each vector (sorted)
    lrow_vec = (vec_panel - bid * W).astype(np.int32)
    counts = np.bincount(bid.astype(np.int64), minlength=n_blocks) if nv \
        else np.zeros(n_blocks, np.int64)
    nonempty = int((counts > 0).sum())

    if capacity is not None:
        K = max(SUBLANES, _round_up(capacity, SUBLANES))
    elif Bal:
        K = balanced_capacity(counts, unbalanced_cap=unbalanced_cap)
    elif S:
        K = split_granularity(nv, nonempty)
    else:
        K = min(_round_up(max(1, counts.max() if nv else 1), SUBLANES),
                _round_up(unbalanced_cap, SUBLANES))

    nch = -(-counts // K)                     # chunks per block (0 if empty)
    C = int(nch.sum())
    if C == 0:                                # degenerate: all-zero matrix
        return PCSR(config, n_rows, n_cols, n_blocks, K,
                    np.zeros(K, np.int32), np.zeros(K, np.int32),
                    np.zeros(1, np.int32), np.ones(1, np.int32),
                    np.zeros((1, V, K), np.float32), nnz, nv, nonempty)

    # emitted block order: ascending for the uniform modes, LPT
    # (descending population, stable) for the balanced schedule
    border = (np.argsort(-counts, kind="stable") if Bal
              else np.arange(n_blocks, dtype=np.int64))
    nch_ord = nch[border]
    starts_ord = np.concatenate([[0], np.cumsum(nch_ord)])
    first_chunk = np.empty(n_blocks, np.int64)
    first_chunk[border] = starts_ord[:-1]     # block id → its first chunk
    trow = np.repeat(border, nch_ord).astype(np.int32)
    init = np.zeros(C, np.int32)
    init[starts_ord[:-1][nch_ord > 0]] = 1

    # slot of each vector: rank within its block → (chunk, slot)
    block_vec_start = np.concatenate([[0], np.cumsum(counts)])
    rank = np.arange(nv, dtype=np.int64) - block_vec_start[bid]
    if Bal:
        # round-robin: every chunk of the block gets ceil- or floor-even
        # share of its vectors → near-uniform per-chunk nnz
        chunk_g = first_chunk[bid] + rank % nch[bid]
        slot = rank // nch[bid]
    else:
        chunk_g = first_chunk[bid] + rank // K
        slot = rank % K

    colidx = np.zeros(C * K, np.int32)
    lrow = np.zeros(C * K, np.int32)
    vals = np.zeros((C, V, K), np.float32)
    pos = chunk_g * K + slot
    colidx[pos] = vec_col.astype(np.int32)
    lrow[pos] = lrow_vec
    vals[chunk_g[:, None], np.arange(V)[None, :], slot[:, None]] = vec_val
    return PCSR(config, n_rows, n_cols, n_blocks, K, colidx, lrow,
                trow, init, vals, nnz, nv, nonempty)


def pad_pcsr(p: PCSR, *, n_rows: int, n_cols: int | None = None,
             num_chunks: int | None = None) -> PCSR:
    """Pad a PCSR to a fixed bucket shape (serving tier).

    Returns a PCSR whose geometry is exactly ``(n_rows, n_cols,
    num_chunks)`` regardless of the input graph, so every request packed
    into one shape bucket produces bit-identical steering-array *shapes*
    — the precondition for one compiled kernel per bucket.  Three kinds
    of chunks are appended after the real ones (prefix property — the
    original chunks come first, verbatim):

    1. one all-padding *coverage* chunk per empty block (``init=1``,
       ascending block id) — the same chunks ``steering(covered=True)``
       would synthesize, materialized eagerly so the padded PCSR has
       **zero** empty blocks and covered == uncovered steering;
    2. ``num_chunks - C - E`` *filler* chunks (``init=0``, all padding)
       targeting the last empty block — they re-visit an already-zeroed
       block and accumulate nothing, bringing the chunk count to the
       bucket ceiling.

    The grouped-``trow`` invariant is preserved (filler directly follows
    its block's coverage chunk), so the lazily recomputed ``fini`` fires
    the fused epilogue exactly once per block.  Row padding relies on the
    caller leaving headroom: callers must size ``n_rows`` so at least one
    block is empty whenever filler is needed (the serve bucket geometry
    adds one always-empty trailing block for exactly this).
    """
    cfg = p.config
    n_cols = n_rows if n_cols is None else n_cols
    if n_rows < p.n_rows or n_cols < p.n_cols:
        raise ValueError(
            f"pad_pcsr target ({n_rows}x{n_cols}) smaller than "
            f"packed matrix ({p.n_rows}x{p.n_cols})")
    n_panels = max(1, _round_up(n_rows, cfg.V) // cfg.V)
    n_blocks = max(1, _round_up(n_panels, cfg.W) // cfg.W)
    covered = np.unique(p.trow.astype(np.int64))
    empty = np.setdiff1d(np.arange(n_blocks, dtype=np.int64), covered)
    E = int(empty.size)
    C = p.num_chunks
    target = C + E if num_chunks is None else int(num_chunks)
    filler = target - C - E
    if filler < 0:
        raise ValueError(
            f"pad_pcsr chunk budget {target} < required {C + E} "
            f"(C={C} real + E={E} coverage)")
    if filler > 0 and E == 0:
        raise ValueError(
            "pad_pcsr needs an empty block to host filler chunks — "
            "size the bucket with at least one spare row block")
    pad = E + filler
    if pad == 0:
        out = PCSR(cfg, n_rows, n_cols, n_blocks, p.K, p.colidx, p.lrow,
                   p.trow, p.init, p.vals, p.nnz, p.nnz_vec,
                   p.n_nonempty_blocks)
        return out
    trow_pad = np.concatenate(
        [empty, np.full(filler, empty[-1] if E else 0, np.int64)])
    trow = np.concatenate([p.trow, trow_pad.astype(np.int32)])
    init = np.concatenate(
        [p.init, np.ones(E, np.int32), np.zeros(filler, np.int32)])
    colidx = np.concatenate([p.colidx, np.zeros(pad * p.K, np.int32)])
    lrow = np.concatenate([p.lrow, np.zeros(pad * p.K, np.int32)])
    vals = np.concatenate(
        [p.vals, np.zeros((pad, cfg.V, p.K), np.float32)])
    return PCSR(cfg, n_rows, n_cols, n_blocks, p.K, colidx, lrow,
                trow, init, vals, p.nnz, p.nnz_vec, p.n_nonempty_blocks)


@dataclass
class PCSRStats:
    """Exact per-(V, W) block-population stats — enough to cost every
    (S, F) choice without materializing the packed arrays."""

    n_rows: int
    n_cols: int
    nnz: int
    V: int
    W: int
    nnz_vec: int
    n_blocks: int
    n_nonempty_blocks: int
    max_block: int
    mean_block: float
    counts_hist: np.ndarray   # per-nonempty-block vector counts

    def chunks_and_slots(self, S: bool, unbalanced_cap: int = UNBALANCED_CAP,
                         B: bool = False):
        """(C, K, slots) of the layout ⟨S, B⟩ would pack — the exact grid
        extents the cost model prices.  ``B=True`` runs the same
        ``balanced_capacity`` search the packer runs, so pricing and
        packing cannot disagree about the balanced chunk geometry."""
        if self.n_nonempty_blocks == 0:
            return 1, SUBLANES, SUBLANES
        if B:
            K = balanced_capacity(self.counts_hist,
                                  unbalanced_cap=unbalanced_cap)
        elif S:
            K = split_granularity(self.nnz_vec, self.n_nonempty_blocks)
        else:
            K = min(_round_up(max(1, self.max_block), SUBLANES),
                    _round_up(unbalanced_cap, SUBLANES))
        nch = -(-self.counts_hist // K)
        C = int(nch.sum())
        return C, K, C * K

    @property
    def padding_ratio(self) -> float:
        if self.nnz_vec == 0:
            return 0.0
        return 1.0 - self.nnz / (self.nnz_vec * self.V)


def pcsr_stats(indptr, indices, n_rows, n_cols, V: int, W: int) -> PCSRStats:
    """Vectorization + block statistics only (cost model / features path)."""
    indptr = np.asarray(indptr, np.int64)
    indices = np.asarray(indices, np.int64)
    nnz = int(indices.shape[0])
    n_panels = max(1, _round_up(n_rows, V) // V)
    n_blocks = max(1, _round_up(n_panels, W) // W)
    if nnz == 0:
        return PCSRStats(n_rows, n_cols, 0, V, W, 0, n_blocks, 0, 0, 0.0,
                         np.zeros(0, np.int64))
    rows = np.repeat(np.arange(n_rows, dtype=np.int64), np.diff(indptr))
    key = (rows // V) * n_cols + indices
    ukey = np.unique(key)
    bid = (ukey // n_cols) // W
    counts = np.bincount(bid, minlength=n_blocks)
    ne = counts[counts > 0]
    return PCSRStats(n_rows, n_cols, nnz, V, W, int(ukey.shape[0]), n_blocks,
                     int(ne.shape[0]), int(ne.max()), float(ne.mean()),
                     ne.astype(np.int64))


def transpose_csr(indptr, indices, data, n_rows, n_cols):
    """CSR of Aᵀ (for the backward SpMM dB = Aᵀ·dC)."""
    indptr = np.asarray(indptr, np.int64)
    indices = np.asarray(indices, np.int64)
    data = np.asarray(data)
    rows = np.repeat(np.arange(n_rows, dtype=np.int64), np.diff(indptr))
    order = np.argsort(indices, kind="stable")
    t_counts = np.bincount(indices, minlength=n_cols)
    t_indptr = np.concatenate([[0], np.cumsum(t_counts)]).astype(np.int64)
    return t_indptr, rows[order], data[order], n_cols, n_rows


def pcsr_slot_coords(p: PCSR):
    """Dense coordinates of every *real* slot entry (stored value ≠ 0).

    Returns ``(rows, cols, flat)`` — the (row, col) of each edge plus its
    flat index into ``vals.reshape(-1)``, the (C, V, K) slot tensor order.
    """
    c, v, k = np.nonzero(p.vals)
    ck = c * p.K + k
    rows = (p.trow[c].astype(np.int64) * p.config.R
            + p.lrow[ck].astype(np.int64) * p.config.V + v)
    cols = p.colidx[ck].astype(np.int64)
    flat = (c * p.config.V + v) * p.K + k
    return rows, cols, flat


def pcsr_to_coo(p: PCSR):
    """Recover the (rows, cols, vals) edge list packed into a PCSR."""
    rows, cols, flat = pcsr_slot_coords(p)
    return rows, cols, p.vals.reshape(-1)[flat]


def transpose_pcsr(p: PCSR, config: SpMMConfig | None = None) -> PCSR:
    """PCSR of Aᵀ under the same (or a given) ⟨W,F,V,S⟩ configuration.

    Built once from the forward PCSR's own edge list (no original CSR
    needed) via ``transpose_csr``-style counting; used by the dedicated GAT
    backward for the ``dK``/``dVf`` SpMMs.
    """
    rows, cols, vals = pcsr_to_coo(p)
    order = np.lexsort((rows, cols))           # CSR of Aᵀ: sort by (col, row)
    t_indptr = np.concatenate(
        [[0], np.cumsum(np.bincount(cols, minlength=p.n_cols))]).astype(np.int64)
    return build_pcsr(t_indptr, rows[order], vals[order],
                      p.n_cols, p.n_rows, config or p.config)


def slot_transfer_map(p: PCSR, p_t: PCSR):
    """Flat-index pair moving per-edge slot values A-layout → Aᵀ-layout.

    For each edge (i, j) of A, ``f_idx`` is its flat position in ``p``'s
    (C, V, K) slot tensor and ``t_idx`` its flat position in ``p_t``'s —
    so ``t.reshape(-1).at[t_idx].set(f.reshape(-1)[f_idx])`` re-lays a slot
    tensor (e.g. softmaxed attention weights) onto the transpose PCSR.
    Padding slots on either side are untouched (they stay zero).
    """
    rows, cols, f_flat = pcsr_slot_coords(p)
    t_rows, t_cols, t_flat = pcsr_slot_coords(p_t)
    key_f = rows * p.n_cols + cols
    key_t = t_cols * p.n_cols + t_rows        # Aᵀ edge (j, i) ↔ A edge (i, j)
    of, ot = np.argsort(key_f), np.argsort(key_t)
    if not np.array_equal(key_f[of], key_t[ot]):
        raise ValueError("PCSR pair does not pack the same edge set")
    return f_flat[of].astype(np.int32), t_flat[ot].astype(np.int32)
