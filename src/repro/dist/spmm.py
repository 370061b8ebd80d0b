"""Distributed SpMM / GAT message over a partitioned PCSR.

``DistGraph`` turns one global adjacency into a mesh of per-shard PCSR
operators: the rows are 1D-partitioned (``partition.py``), each shard's
local CSR gets its *own* ⟨W,F,V,S⟩ configuration — chosen by
``CostModel.best`` (or a trained decider) on that shard's features, and
priced per head count when the graph aggregates a multi-head GAT — and
the per-shard packed arrays are padded to uniform shapes and sharded
over a ``("parts",)`` device mesh (``packing.py``).

Execution is one SPMD ``shard_map`` program:

1. **halo exchange** (``halo.py``) — one compacted ``all_gather`` brings
   the remote source rows each shard needs; they concatenate after the
   local feature block to form the extended column space the local PCSR
   indexes.  SpMM and SDDMM on the shard reuse the same exchange.
2. **per-shard compute** — ``lax.switch`` on ``axis_index("parts")``
   dispatches to a per-partition branch closed over that shard's
   *static* PCSR shapes (C, K, V, R, n_blocks), so partitions genuinely
   run different configurations inside a single SPMD program.  Branches
   run ``core.engine``'s operators over the shard's steering.
3. **``dist_spmm`` backward** — a ``custom_vjp`` whose backward runs the
   per-shard *transpose* PCSR (``dB_ext = A_pᵀ·dC_p``) and scatters the
   halo block of the gradient back to its owner shards through
   ``halo_scatter_back`` (scatter → ``psum_scatter`` → local add), the
   exact transpose of the forward exchange.

``DistGraph(overlap=True)`` switches the SpMM paths to the **halo/compute
overlap** decomposition: each shard's matrix splits into a *local* part
(owned source columns) and a *halo* part (remote columns, operating
directly on the gathered buffer) — ``partition.split_local_halo`` — each
under its own cost-model-selected config.  The local SpMM has no data
dependency on the ``all_gather``, so the XLA scheduler hides the gather
latency behind it; the backward mirrors this by issuing the halo
gradients' ``psum_scatter`` before the local transpose SpMM runs.  See
docs/DISTRIBUTED.md §Overlap for the timeline.

``DistGraph.fused`` is the epilogue-fused distributed aggregation:
scale/bias/activation applied per shard inside the SPMD program
(in-kernel on Pallas branches via the covered steering pack's ``fini``
arrays, XLA-fused into the engine branches) — no global elementwise pass
follows the halo'd SpMM.  Its backward runs ONE shard_map program that
folds the ``dbias`` reduction in as a ``psum`` (a replicated output of
the same SPMD program that computes ``dB``), so nothing about the fused
backward happens outside the mesh.

``dist_gat_message`` (``gat.py``) runs the attention message per shard —
multi-head, two Pallas kernels per shard forward and an all-Pallas
flash-recompute backward on the Pallas backend.  Row partitioning keeps
every destination row's full edge set on one shard, so edge softmax
needs no communication — only the joint K/Vf halo exchange (and, in the
backward, the dK/dVf halo gradient scatter) crosses the mesh.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import PartitionSpec

from repro.core import (CostModel, CSRMatrix, SpMMConfig, build_pcsr,
                        config_space, extract_features)
from repro.core.engine import apply_epilogue, epilogue_grad, steered_spmm
from repro.kernels import resolve_backend
from repro.obs import trace as _obs_trace

from .gat import build_dist_gat, build_gat_pack
from .halo import HaloSpec, build_halo, halo_exchange, halo_scatter_back
from .packing import (AXIS, PackedShards, pack_shards, shard_map_2d,
                      shard_steering)
from .partition import RowPartition, partition_csr, split_local_halo


def _spmm_branch(pcsr, *, backend: str, interpret: bool,
                 epilogue: bool = False, activation: str = "none"):
    """Branch computing ``A_p · B_ext`` with shard-``p``-static shapes:
    ``core.engine.steered_spmm`` over the shard's steering.

    With ``epilogue=True`` the branch takes two extra operands — the
    shard's per-row scale column and the replicated per-feature bias row —
    and applies scale/bias/activation per shard: in-kernel on the Pallas
    backend (the fused epilogue), XLA-fused into the SPMD program on the
    engine backend."""
    def branch(colidx, lrow, trow, init, fini, vals, b_ext, *ep):
        spmm = steered_spmm(
            shard_steering(pcsr, colidx, lrow, trow, init, fini, vals),
            pcsr, backend=backend, interpret=interpret)
        if epilogue:
            return spmm(b_ext, scale=ep[0][:, 0], bias=ep[1][0],
                        activation=activation)
        return spmm(b_ext)
    return branch


# ----------------------------------------------------------- DistGraph
class DistGraph:
    """Partitioned graph operator: per-shard adaptive PCSR on a mesh.

    Configuration resolution per shard: explicit ``configs`` (one or a
    per-shard list) > ``decider`` prediction on the shard's features >
    ``CostModel.best`` on the shard's local CSR with ``op``/``heads``
    pricing — so a power-law graph's hub shard and tail shards pick
    *different* ⟨W,F,V,S⟩, the cross-shard form of the paper's
    adaptivity claim.

    Parameters
    ----------
    csr : CSRMatrix
        The global (square) adjacency; rows are destination nodes.
    dim : int
        Feature width the configs are priced for.
    n_parts : int
        Number of row shards (= mesh devices on first call).
    strategy : ``"balanced"`` (equal-nnz boundaries) or ``"contiguous"``.
    calibration : optional ``repro.core.calibrate.CalibrationResult`` (or
        artifact path) — the per-shard ``CostModel.best`` selection then
        prices through coefficients fitted to measured kernel time on
        this host instead of the hand-set analytic constants.
    heads : int
        Head count the cost model prices the configs for
        (``CostModel.best(..., H=heads)``): head tiling multiplies the
        grid and shrinks the per-head lane width, so the per-shard
        optimum genuinely changes with H.  ``gat_message`` accepts any
        head count at call time regardless.
    overlap : bool
        Run the SpMM paths under the halo/compute-overlap decomposition
        (local + halo sub-matrices per shard, each with its own config;
        the gather hides behind the local SpMM).  GAT's attention chain
        (gather → SDDMM → softmax → SpMM) leaves nothing independent of
        the gather to overlap with, so ``gat_message`` always runs the
        joint-exchange path.
    backend : ``"engine"`` (pure JAX) or ``"pallas"`` (TPU kernels);
        ``None`` lets the platform choose (``repro.kernels.resolve_backend``:
        compiled kernels on a TPU, the engine elsewhere).
    op : operator the per-shard configs are priced for
        (``"spmm"`` | ``"sddmm"`` | ``"gat"``).

    Construction is a device-free host-side plan (partition, halo maps,
    per-shard config selection, packing); the mesh is resolved on the
    first call.
    """

    def __init__(self, csr: CSRMatrix, dim: int, n_parts: int, *,
                 strategy: str = "balanced",
                 configs=None,
                 decider=None,
                 calibration=None,
                 mesh=None,
                 backend: str | None = None,
                 interpret: bool | None = None,
                 op: str = "spmm",
                 heads: int = 1,
                 overlap: bool = False,
                 max_f: int = 4):
        self.csr = csr
        self.dim = dim
        self.backend, self.interpret = resolve_backend(backend, interpret)
        self.heads = heads
        self.overlap = overlap
        self.part: RowPartition = partition_csr(csr, n_parts, strategy)
        self.halo: HaloSpec = build_halo(self.part)
        self._mesh = mesh                  # resolved lazily: the host-side
        # plan (partition, configs, packing) needs no devices at all

        # per-shard selection prices through a calibration artifact when
        # one is given (path or CalibrationResult) — the per-shard
        # adaptivity claim is only honest under fitted-to-hardware prices
        if calibration is not None and not hasattr(calibration, "price"):
            from repro.core.calibrate import CalibrationResult
            calibration = CalibrationResult.load(calibration)
        self.calibration = calibration

        space = config_space(dim, max_f)
        self.predicted_times: list = []
        if configs is None:
            if decider is not None:
                with _obs_trace.span("dist.select_configs", picker="decider",
                                     n_parts=n_parts):
                    self.configs = [
                        decider.predict(extract_features(s.csr), dim)
                        for s in self.part.shards]
            else:
                self.configs = []
                with _obs_trace.span("dist.select_configs",
                                     picker="cost_model", n_parts=n_parts):
                    for s in self.part.shards:
                        cfg, t = CostModel(s.csr,
                                           calibration=calibration).best(
                            dim, space, op=op, H=heads)
                        self.configs.append(cfg)
                        self.predicted_times.append(t)
        elif isinstance(configs, SpMMConfig):
            self.configs = [configs] * n_parts
        else:
            self.configs = list(configs)
            if len(self.configs) != n_parts:
                raise ValueError("configs list must have one entry per shard")

        with _obs_trace.span("dist.pack", n_parts=n_parts):
            self._fwd = pack_shards(
                [build_pcsr(s.csr.indptr, s.csr.indices, s.csr.data,
                            s.csr.n_rows, s.csr.n_cols, cfg)
                 for s, cfg in zip(self.part.shards, self.configs)])

        # overlap mode: split every shard into local + halo sub-matrices,
        # each under its own cost-model-selected config (the halo part of
        # a power-law shard is typically much sparser than the local one)
        self.overlap_configs: list = []
        self._split_csrs: list = []
        self._loc = self._halo_pack = None
        if overlap:
            loc_pcsrs, halo_pcsrs = [], []
            for i, s in enumerate(self.part.shards):
                loc, hal = split_local_halo(s, self.part)
                self._split_csrs.append((loc, hal))
                if configs is not None:
                    lc = hc = self.configs[i]
                elif decider is not None:
                    lc = decider.predict(extract_features(loc), dim)
                    hc = decider.predict(extract_features(hal), dim)
                else:
                    lc, _ = CostModel(loc, calibration=calibration).best(
                        dim, space, H=heads)
                    hc, _ = CostModel(hal, calibration=calibration).best(
                        dim, space, H=heads)
                self.overlap_configs.append((lc, hc))
                loc_pcsrs.append(build_pcsr(loc.indptr, loc.indices,
                                            loc.data, loc.n_rows,
                                            loc.n_cols, lc))
                halo_pcsrs.append(build_pcsr(hal.indptr, hal.indices,
                                             hal.data, hal.n_rows,
                                             hal.n_cols, hc))
            self._loc = pack_shards(loc_pcsrs)
            self._halo_pack = pack_shards(halo_pcsrs)
        self._bwd_pack = None              # transpose PCSRs built on first
        self._bwd_split_pack = None        # backward only — forward-only /
        # GAT (engine-autodiff) use skips them
        self._send_idx = jnp.asarray(self.halo.send_idx)
        self._halo_src = jnp.asarray(self.halo.halo_src)

        # global ↔ padded-layout row maps
        g = np.arange(self.part.n_global, dtype=np.int64)
        pad_pos = self.part.pad_position(g)
        n_pad = self.part.n_parts * self.part.rows_pad
        pad_src = np.zeros(n_pad, np.int32)
        pad_valid = np.zeros(n_pad, bool)
        pad_src[pad_pos] = g
        pad_valid[pad_pos] = True
        self._pad_pos = jnp.asarray(pad_pos.astype(np.int32))
        self._pad_src = jnp.asarray(pad_src)
        self._pad_valid = jnp.asarray(pad_valid)

        self._spmm_fn = None               # built lazily (first call) so a
        self._gat_fns: dict = {}           # host-side plan needs no devices
        self._gat_packs: dict = {}         # per-H head-tiled GAT packs
        self._fused_fns: dict = {}         # per-activation fused programs
        self._fused_bwd_fns: dict = {}     # per-activation fused backwards
        self._bwd_fn = None                # shared transpose-path shard_map

    @property
    def mesh(self):
        if self._mesh is None:
            from repro.launch.mesh import make_partition_mesh
            self._mesh = make_partition_mesh(self.part.n_parts)
        return self._mesh

    @property
    def _bwd(self) -> PackedShards:
        """Transpose PCSRs of the full per-shard matrices (lazy)."""
        if self._bwd_pack is None:
            bwd = []
            for s, cfg in zip(self.part.shards, self.configs):
                t = s.csr.transpose()      # (ext_cols, rows_pad)
                bwd.append(build_pcsr(t.indptr, t.indices, t.data,
                                      t.n_rows, t.n_cols, cfg))
            self._bwd_pack = pack_shards(bwd)
        return self._bwd_pack

    @property
    def _bwd_split(self):
        """Transpose PCSRs of the local/halo sub-matrices (overlap mode,
        lazy): ``A_locᵀ`` is (rows_pad, rows_pad), ``A_haloᵀ`` is
        (halo_pad, rows_pad) — its output IS the halo gradient block."""
        if self._bwd_split_pack is None:
            loc_t, halo_t = [], []
            for (loc, hal), (lc, hc) in zip(self._split_csrs,
                                            self.overlap_configs):
                lt = loc.transpose()
                ht = hal.transpose()
                loc_t.append(build_pcsr(lt.indptr, lt.indices, lt.data,
                                        lt.n_rows, lt.n_cols, lc))
                halo_t.append(build_pcsr(ht.indptr, ht.indices, ht.data,
                                         ht.n_rows, ht.n_cols, hc))
            self._bwd_split_pack = (pack_shards(loc_t), pack_shards(halo_t))
        return self._bwd_split_pack

    def gat_pack(self, H: int):
        """Head-tiled covered steering pack for an ``H``-head GAT
        (cached per head count).  H = 1 reuses the graph's own forward
        pack — the covered arrays are identical."""
        if H not in self._gat_packs:
            self._gat_packs[H] = build_gat_pack(
                self._fwd.pcsrs, H, fwd=self._fwd if H == 1 else None)
        return self._gat_packs[H]

    # ---------------------------------------------------------- layout
    def pad(self, x):
        """(n_global, d) → (P·rows_pad, d) padded mesh layout."""
        x = jnp.asarray(x)
        return jnp.where(self._pad_valid[:, None],
                         jnp.take(x, self._pad_src, axis=0), 0)

    def unpad(self, x):
        """(P·rows_pad, d) padded mesh layout → (n_global, d)."""
        return jnp.take(x, self._pad_pos, axis=0)

    def pad_heads(self, x):
        """(H, n_global, d) head stack → (P·rows_pad, H·d) merged padded
        mesh layout (heads ride the feature axis so every mesh operand
        stays rank-2; branches split them back out)."""
        x = jnp.asarray(x)
        return self.pad(jnp.transpose(x, (1, 0, 2)).reshape(x.shape[1], -1))

    def unpad_heads(self, x, H: int):
        """(P·rows_pad, H·d) merged padded layout → (H, n_global, d)."""
        y = self.unpad(x)
        return y.reshape(y.shape[0], H, -1).transpose(1, 0, 2)

    # -------------------------------------------------------- dynamics
    def refresh(self, new_csr: CSRMatrix, *, threshold=None):
        """Swap in a mutated adjacency with **per-shard re-pack**: only
        shards whose local subgraph changed rebuild their steering pack
        (and re-pick their config when their feature snapshot drifted
        past ``threshold``); unchanged shards keep their PCSR objects
        and the SPMD program structure is untouched.  Returns a
        ``repro.dynamic.ShardRefreshReport``.  See
        ``repro.dynamic.refresh_dist_graph`` / docs/DYNAMIC.md."""
        from repro.dynamic.dist import refresh_dist_graph
        return refresh_dist_graph(self, new_csr, threshold=threshold)

    # ------------------------------------------------------- operators
    def spmm(self, B):
        """``C = A·B`` distributed; ``(n_global, d)`` in and out.

        A ``custom_vjp``: the backward runs the per-shard transpose PCSR
        and scatters halo gradients home (``overlap=True`` additionally
        hides the forward gather behind the local sub-SpMM and the
        backward ``psum_scatter`` behind the local transpose SpMM)."""
        if self._spmm_fn is None:
            self._spmm_fn = _build_dist_spmm(self)
        return self._spmm_fn(B)

    __call__ = spmm

    def fused(self, B, scale=None, bias=None, activation: str = "none"):
        """Epilogue-fused distributed aggregation
        ``act(scale ⊙ (A·B) + bias)`` — scale/bias/activation are applied
        *per shard inside the SPMD program* (in-kernel on the Pallas
        backend, XLA-fused into the branch on the engine backend), so no
        separate global elementwise pass follows the halo'd SpMM.  Under
        ``overlap=True`` the epilogue applies per shard after the
        local+halo add (XLA-fused; the in-kernel epilogue is traded for
        the hidden gather).  Differentiable in ``B`` and ``bias`` —
        the backward is ONE shard_map program returning ``dB`` and a
        ``psum``-replicated ``dbias``; ``scale`` (degree data) is a
        constant."""
        if activation not in self._fused_fns:
            self._fused_fns[activation] = _build_dist_fused_spmm(
                self, activation=activation)
        n, d = self.part.n_global, jnp.shape(B)[-1]
        scale = jnp.ones(n, jnp.float32) if scale is None \
            else jnp.asarray(scale)
        bias_arr = jnp.zeros(d, jnp.float32) if bias is None \
            else jnp.asarray(bias)
        out = self._fused_fns[activation](B, scale, bias_arr)
        return out

    def _fused_bwd(self, activation: str):
        """The fused backward SPMD program (cached per activation):
        ``(out, scale, dOut) -> (dB, dbias)`` with the ``dbias``
        reduction folded into the transpose shard_map as a ``psum``."""
        if activation not in self._fused_bwd_fns:
            self._fused_bwd_fns[activation] = _build_dist_fused_bwd(
                self, activation=activation)
        return self._fused_bwd_fns[activation]

    def gat_message(self, Q, K, Vf, *, slope: float = 0.2):
        """Distributed GAT attention message.

        ``(n, d)`` operands run single-head; ``(H, n, d)`` stacks batch
        every head through the per-shard head-tiled steering arrays in
        ONE SPMD program — on the Pallas backend that is exactly two
        kernels per shard forward (fused SDDMM→softmax-stats + prologue
        SpMM) and an all-Pallas flash-recompute backward with halo
        gradient scatter-back; the engine backend is natively
        differentiable.  See ``repro.dist.gat`` for the pipeline."""
        Q, K, Vf = (jnp.asarray(x) for x in (Q, K, Vf))
        multi = Q.ndim == 3
        H = Q.shape[0] if multi else 1
        key = (slope, H)
        if key not in self._gat_fns:
            self._gat_fns[key] = build_dist_gat(self, slope=slope, H=H)
        fn = self._gat_fns[key]
        if multi:
            return fn(Q, K, Vf)
        return fn(Q[None], K[None], Vf[None])[0]


# ------------------------------------------------------ transpose core
def _bwd_core(g: DistGraph):
    """The per-shard transpose-path core ``dc -> dB_local`` (halo
    gradient block scattered home), shared by the plain and the
    epilogue-fused distributed backwards.

    Non-overlap graphs run one transpose SpMM over the extended column
    space and scatter its halo block back.  Overlap graphs run the split
    form: the halo-side transpose SpMM first, whose ``psum_scatter``
    collective then overlaps with the local transpose SpMM (no data
    dependency between them).  Returns ``(core, ops)`` where ``ops`` are
    the mesh-sharded operand arrays the enclosing shard_map must be
    handed after the gradient operand(s)."""
    rows_pad = g.part.rows_pad
    n_parts, max_send = g.halo.n_parts, g.halo.max_send

    def scatter(d_halo, sidx, hsrc):
        return halo_scatter_back(d_halo, sidx, hsrc, n_parts=n_parts,
                                 max_send=max_send, rows_pad=rows_pad,
                                 axis_name=AXIS)

    if not g.overlap:
        branches = [_spmm_branch(p, backend=g.backend, interpret=g.interpret)
                    for p in g._bwd.pcsrs]

        def core(dc, colidx, lrow, trow, init, fini, vals, sidx, hsrc):
            i = jax.lax.axis_index(AXIS)
            d_ext = jax.lax.switch(i, branches, colidx[0], lrow[0],
                                   trow[0], init[0], fini[0], vals[0], dc)
            back = scatter(d_ext[rows_pad:], sidx[0], hsrc[0])
            return d_ext[:rows_pad] + back

        return core, (*g._bwd.arrays, g._send_idx, g._halo_src)

    loc_t, halo_t = g._bwd_split
    loc_branches = [_spmm_branch(p, backend=g.backend,
                                 interpret=g.interpret)
                    for p in loc_t.pcsrs]
    halo_branches = [_spmm_branch(p, backend=g.backend, interpret=g.interpret)
                     for p in halo_t.pcsrs]

    def core(dc, lc, ll, lt, li, lf, lv, hc, hl, ht, hi, hf, hv,
             sidx, hsrc):
        i = jax.lax.axis_index(AXIS)
        # halo-side transpose first: its scatter-back collective then
        # overlaps with the local transpose SpMM (no data dependency)
        d_halo = jax.lax.switch(i, halo_branches, hc[0], hl[0], ht[0],
                                hi[0], hf[0], hv[0], dc)
        back = scatter(d_halo, sidx[0], hsrc[0])
        d_loc = jax.lax.switch(i, loc_branches, lc[0], ll[0], lt[0],
                               li[0], lf[0], lv[0], dc)
        return d_loc + back

    return core, (*loc_t.arrays, *halo_t.arrays, g._send_idx, g._halo_src)


def _dist_bwd_transpose(g: DistGraph):
    """The transpose-path backward ``dB = Aᵀ·dC`` with halo scatter-back,
    built lazily on the first backward trace (forward-only use never
    builds the transpose PCSRs) and shared between the plain and the
    epilogue-fused distributed SpMM."""
    if g._bwd_fn is None:
        core, ops = _bwd_core(g)
        sm = shard_map_2d(core, g.mesh, 1 + len(ops))

        def run(dC):
            return g.unpad(sm(g.pad(dC), *ops))

        g._bwd_fn = jax.jit(run)   # cache the SPMD trace across steps
    return g._bwd_fn


def _build_dist_fused_bwd(g: DistGraph, *, activation: str):
    """The fused-epilogue backward as ONE SPMD program: per shard

        dpre  = dOut ⊙ act'(out)
        dbias = psum(Σ_local-rows dpre)        (replicated output)
        dB    = transpose-core(scale ⊙ dpre)   (halo block scattered home)

    The ``dbias`` reduction is an in-program ``psum`` down the mesh axis
    — NOT a global reduce outside the SPMD program — so the whole fused
    backward lives in one shard_map whatever the mesh size."""
    core, ops = _bwd_core(g)

    def body(dout, out, sc, *rest):
        dpre = epilogue_grad(out, dout, activation)
        dbias = jax.lax.psum(jnp.sum(dpre, axis=0), AXIS)
        return core(dpre * sc, *rest), dbias

    out_specs = (PartitionSpec(AXIS, None), PartitionSpec(None))
    sm = shard_map_2d(body, g.mesh, 3 + len(ops), out_specs=out_specs)

    @jax.jit
    def run(out, scale, dOut):
        dB, dbias = sm(g.pad(dOut), g.pad(out), g.pad(scale[:, None]),
                       *ops)
        return g.unpad(dB), dbias

    return run


# ------------------------------------------------------- forward paths
def _build_dist_spmm(g: DistGraph):
    """The ``custom_vjp`` distributed SpMM closed over one DistGraph."""
    if g.overlap:
        run_fwd = _build_overlap_fwd(g)
    else:
        fwd_branches = [_spmm_branch(p, backend=g.backend,
                                     interpret=g.interpret)
                        for p in g._fwd.pcsrs]

        def fwd_body(b, colidx, lrow, trow, init, fini, vals, sidx, hsrc):
            halo = halo_exchange(b, sidx[0], hsrc[0], axis_name=AXIS)
            b_ext = jnp.concatenate([b, halo], axis=0)
            i = jax.lax.axis_index(AXIS)
            return jax.lax.switch(i, fwd_branches, colidx[0], lrow[0],
                                  trow[0], init[0], fini[0], vals[0],
                                  b_ext)

        fwd_sm = shard_map_2d(fwd_body, g.mesh, 9)

        def run_fwd(B):
            out = fwd_sm(g.pad(B), *g._fwd.arrays,
                         g._send_idx, g._halo_src)
            return g.unpad(out)

    @jax.custom_vjp
    def f(B):
        return run_fwd(B)

    def f_fwd(B):
        return run_fwd(B), None

    def f_bwd(_, dC):
        return (_dist_bwd_transpose(g)(dC),)

    f.defvjp(f_fwd, f_bwd)
    return jax.jit(f)          # cache the SPMD trace across training steps


def _build_overlap_fwd(g: DistGraph, *, epilogue: bool = False,
                       activation: str = "none"):
    """The overlap forward: ``A_p·B_ext = A_loc·B_loc + A_halo·halo``.

    The ``all_gather`` is issued first; the local sub-SpMM takes only the
    shard's own feature block, so the XLA latency-hiding scheduler runs
    it concurrently with the collective — the gather's wire time hides
    behind local compute and only the (much smaller) halo sub-SpMM waits
    for the landed rows.  With ``epilogue=True`` scale/bias/activation
    apply per shard after the add (XLA-fused; an in-kernel epilogue
    would force the two partial SpMMs to accumulate in one kernel)."""
    loc_branches = [_spmm_branch(p, backend=g.backend, interpret=g.interpret)
                    for p in g._loc.pcsrs]
    halo_branches = [_spmm_branch(p, backend=g.backend, interpret=g.interpret)
                     for p in g._halo_pack.pcsrs]

    def body(b, lc, ll, lt, li, lf, lv, hc, hl, ht, hi, hf, hv,
             sidx, hsrc, *ep):
        halo = halo_exchange(b, sidx[0], hsrc[0], axis_name=AXIS)
        i = jax.lax.axis_index(AXIS)
        out_loc = jax.lax.switch(i, loc_branches, lc[0], ll[0], lt[0],
                                 li[0], lf[0], lv[0], b)
        out_halo = jax.lax.switch(i, halo_branches, hc[0], hl[0], ht[0],
                                  hi[0], hf[0], hv[0], halo)
        out = out_loc + out_halo
        if epilogue:
            out = apply_epilogue(out, ep[0][:, 0], ep[1][0], activation)
        return out

    n_in = 15 + (2 if epilogue else 0)
    replicated = (16,) if epilogue else ()
    sm = shard_map_2d(body, g.mesh, n_in, replicated=replicated)
    ops = (*g._loc.arrays, *g._halo_pack.arrays, g._send_idx, g._halo_src)

    def run_fwd(B, *ep):
        return g.unpad(sm(g.pad(B), *ops, *ep))

    return run_fwd


def _build_dist_fused_spmm(g: DistGraph, *, activation: str):
    """Epilogue-fused distributed SpMM: one SPMD program whose per-shard
    branches apply scale/bias/activation where the output is produced —
    in-kernel (Pallas) or XLA-fused into the branch (engine) — so the
    fused distributed GCN layer runs no global elementwise pass after the
    halo'd SpMM.  A ``custom_vjp`` over (B, bias): the backward is one
    shard_map program computing ``dB`` through the shared transpose path
    on ``scale ⊙ (dOut ⊙ act'(out))`` with the ``dbias`` reduction folded
    in as a ``psum`` (see ``_build_dist_fused_bwd``)."""
    if g.overlap:
        overlap_fwd = _build_overlap_fwd(g, epilogue=True,
                                         activation=activation)

        @jax.jit
        def run_fwd(B, scale, bias):
            return overlap_fwd(B, g.pad(scale[:, None]), bias[None, :])
    else:
        branches = [_spmm_branch(p, backend=g.backend,
                                 interpret=g.interpret, epilogue=True,
                                 activation=activation)
                    for p in g._fwd.pcsrs]

        def body(b, colidx, lrow, trow, init, fini, vals, sidx, hsrc,
                 sc, bi):
            halo = halo_exchange(b, sidx[0], hsrc[0], axis_name=AXIS)
            b_ext = jnp.concatenate([b, halo], axis=0)
            i = jax.lax.axis_index(AXIS)
            return jax.lax.switch(i, branches, colidx[0], lrow[0],
                                  trow[0], init[0], fini[0], vals[0],
                                  b_ext, sc, bi)

        sm = shard_map_2d(body, g.mesh, 11, replicated=(10,))

        @jax.jit                       # cache the SPMD trace across steps;
        def run_fwd(B, scale, bias):   # the custom_vjp wrapper stays unjitted
            out = sm(g.pad(B), *g._fwd.arrays, g._send_idx, g._halo_src,
                     g.pad(scale[:, None]), bias[None, :])
            return g.unpad(out)

    @jax.custom_vjp
    def f(B, scale, bias):
        return run_fwd(B, scale, bias)

    def f_fwd(B, scale, bias):
        out = run_fwd(B, scale, bias)
        return out, (out, scale)

    def f_bwd(res, dOut):
        out, scale = res
        dB, dbias = g._fused_bwd(activation)(out, scale, dOut)
        # scale is graph data (degree norms), not a trained parameter
        return dB, jnp.zeros_like(scale), dbias

    f.defvjp(f_fwd, f_bwd)
    return f


# ------------------------------------------------------ functional API
def dist_spmm(graph: DistGraph, B):
    """``C = A·B`` over a partitioned graph; ``(n, d)`` global in and
    out.  The backward is the explicit per-shard transpose path with halo
    gradient scatter-back (see ``DistGraph.spmm``)."""
    return graph.spmm(B)


def dist_gat_message(graph: DistGraph, Q, K, Vf, *, slope: float = 0.2):
    """Distributed SDDMM → LeakyReLU → edge softmax → SpMM message.

    ``(n, d)`` operands run single-head; ``(H, n, d)`` stacks run every
    head through one head-tiled SPMD program.  On the Pallas backend the
    forward is exactly two kernels per shard and the backward is the
    all-Pallas flash recompute (``repro.dist.gat``); on the engine
    backend the program is natively differentiable."""
    return graph.gat_message(Q, K, Vf, slope=slope)
