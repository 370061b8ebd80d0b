"""Distributed GAT message: multi-head shards, the GAT pipeline per shard.

The GAT attention pipeline — the two-kernel forward and the flash-style
all-kernel backward — is written once, in ``core.engine`` (``gat_forward``
/ ``gat_backward``), over steering arrays.  This module runs it **per
shard inside one SPMD ``shard_map`` program**, multi-head, and keeps only
what is distributed:

* **forward** — K/Vf are halo-exchanged jointly (one ``all_gather``
  serves every head of both operands: heads travel merged as
  ``(rows, H·d)`` columns), then each shard's branch splits the heads
  and runs ``gat_forward`` over its OWN head-tiled steering
  (``PCSR.steering(H, covered=True)``, packed per partition by
  ``packing.pack_shards(H=)``; ``_uncov`` views give the SDDMM's
  uncovered arrays) — on Pallas exactly two kernels per shard.
* **backward** — a ``custom_vjp`` (Pallas backend): the forward pads
  its residuals (raw logits and tile-aligned row stats) to uniform
  widths so they cross the ``shard_map`` boundary; the backward program
  re-exchanges the K/Vf halo (recompute over memory), runs
  ``gat_backward`` per shard and scatters the halo blocks of dK/dVf back
  to their owner shards through ``halo_scatter_back`` — no engine
  fallback anywhere (enforced by test).

Row partitioning keeps every destination row's full edge set on one
shard, so the softmax — forward stats and backward vjp alike — never
communicates; only the operand halo exchange and the gradient
scatter-back cross the mesh.

The engine backend runs the same forward as one natively-differentiable
program; its halo gradients flow back through the autodiff transpose of
``all_gather`` (a ``psum_scatter``).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.engine import gat_backward, gat_forward
from repro.core.pcsr import (LANES, SUBLANES, slot_transfer_map,
                             transpose_pcsr)
from repro.obs import trace as _obs_trace

from .halo import halo_exchange, halo_scatter_back
from .packing import (AXIS, PackedShards, pack_shards, shard_map_2d,
                      shard_steering)

# transfer-map padding: out-of-bounds target slots are dropped by the
# scatter (mode="drop"), so padded map entries can never alias real slots
T_SENTINEL = np.int32(2**31 - 1)


def head_split(x2, H: int):
    """(n, H·d) merged mesh layout → (H, n, d) head stack."""
    n = x2.shape[0]
    return x2.reshape(n, H, -1).transpose(1, 0, 2)


def head_merge(x3):
    """(H, n, d) head stack → (n, H·d) merged mesh layout."""
    H, n, d = x3.shape
    return x3.transpose(1, 0, 2).reshape(n, H * d)


def _uncov(st, H: int, pcsr):
    """The uncovered head-tiled view of a shard's covered head-tiled
    steering: per head, the real chunks are the ``[:C]`` prefix of that
    head's ``C_cov``-chunk segment (coverage chunks pack last)."""
    Cc, C = pcsr.covered_num_chunks, pcsr.num_chunks
    if Cc == C:
        return st

    def cut(a):
        per = a.shape[0] // (H * Cc)
        return a.reshape((H, Cc * per) + a.shape[1:])[:, :C * per] \
            .reshape((H * C * per,) + a.shape[1:])
    return {k: cut(v) for k, v in st.items()}


@dataclass
class GatShardPack:
    """Head-tiled covered steering packs for the distributed GAT.

    ``fwd`` packs every shard's ``steering(H, covered=True)`` arrays;
    ``logits_pad``/``stats_pad`` are the uniform residual widths the
    forward branches pad their (per-shard-sized) logits and row stats to
    so they cross the ``shard_map`` boundary as stacked ``(P, ·)``
    tensors.  The backward side — transpose PCSRs packed the same way
    plus the per-edge slot transfer maps — is built lazily on the first
    backward trace (forward-only use never pays for it)."""

    H: int
    fwd: PackedShards
    logits_pad: int              # max over shards of H·C·V·K
    stats_pad: int               # max over shards of H·nb·SUBLANES·LANES
    bwd: Optional[PackedShards] = None    # transpose PCSRs (lazy)
    f_idx: Optional[jnp.ndarray] = None   # (P, L) A-layout slot positions
    t_idx: Optional[jnp.ndarray] = None   # (P, L) Aᵀ-layout positions


def build_gat_pack(pcsrs, H: int,
                   fwd: Optional[PackedShards] = None) -> GatShardPack:
    """Pack the shards' head-tiled covered steering for one head count.
    Pass an existing H=1 pack as ``fwd`` to reuse it (the single-head
    covered arrays are identical — no second device-resident copy)."""
    with _obs_trace.span("gat.pack", H=H, n_parts=len(pcsrs),
                         reused=fwd is not None):
        return GatShardPack(
            H, fwd if fwd is not None else pack_shards(pcsrs, H=H),
            logits_pad=max(H * p.num_chunks * p.config.V * p.K
                           for p in pcsrs),
            stats_pad=max(H * p.n_blocks * SUBLANES * LANES for p in pcsrs))


def ensure_gat_bwd_pack(pack: GatShardPack) -> None:
    """Build the transpose-PCSR pack + slot transfer maps (idempotent)."""
    if pack.bwd is not None:
        return
    with _obs_trace.span("gat.bwd_pack", H=pack.H,
                         n_parts=len(pack.fwd.pcsrs)):
        _build_gat_bwd_pack(pack)


def _build_gat_bwd_pack(pack: GatShardPack) -> None:
    pts = [transpose_pcsr(p) for p in pack.fwd.pcsrs]
    maps = [slot_transfer_map(p, pt)
            for p, pt in zip(pack.fwd.pcsrs, pts)]
    P = len(pts)
    L = max([m[0].size for m in maps] + [1])
    f = np.zeros((P, L), np.int32)
    t = np.full((P, L), T_SENTINEL, np.int32)
    for i, (fi, ti) in enumerate(maps):
        f[i, :fi.size] = fi
        t[i, :ti.size] = ti
    pack.bwd = pack_shards(pts, H=pack.H)
    # built lazily on the first backward trace — keep the cached maps
    # concrete so later traces can reuse them (see packing.pack_shards)
    with jax.ensure_compile_time_eval():
        pack.f_idx, pack.t_idx = jnp.asarray(f), jnp.asarray(t)


# ------------------------------------------------------------ branches
def _fwd_branch(pcsr, *, H: int, slope: float, backend: str,
                interpret: bool, logits_pad: int, stats_pad: int):
    """``gat_forward`` with shard-static shapes.  On Pallas it returns
    (out, logits, rowmax, rowsum), the latter three flat and padded to
    the pack-uniform residual widths (the backward's inputs)."""
    def branch(colidx, lrow, trow, init, fini, vals, q2, kx2, vfx2):
        st = shard_steering(pcsr, colidx, lrow, trow, init, fini, vals, H)
        out, res = gat_forward(st, _uncov(st, H, pcsr), head_split(q2, H),
                               head_split(kx2, H), head_split(vfx2, H),
                               geom=pcsr, backend=backend,
                               interpret=interpret, slope=slope)
        if res is None:
            return head_merge(out)
        pad1 = lambda x, L: jnp.pad(x.reshape(-1), (0, L - x.size))[None, :]
        return (head_merge(out),) + tuple(
            pad1(x, L) for x, L in zip(res, (logits_pad, stats_pad,
                                             stats_pad)))
    return branch


def _bwd_branch(pcsr, pcsr_t, *, H: int, slope: float, interpret: bool):
    """``gat_backward`` with shard-static shapes: the residuals are cut
    back from their uniform widths; dK/dVf come back over the extended
    column space, and the caller scatters their halo blocks home."""
    C, K, V, nb = pcsr.num_chunks, pcsr.K, pcsr.config.V, pcsr.n_blocks

    def branch(fcol, flrow, ftrow, finit, ffini, fvals,
               tcol, tlrow, ttrow, tinit, tfini, tvals,
               fidx, tidx, do2, q2, kx2, vfx2, lgf, rmf, rsf):
        st = shard_steering(pcsr, fcol, flrow, ftrow, finit, ffini, fvals, H)
        st_t = shard_steering(pcsr_t, tcol, tlrow, ttrow, tinit, tfini,
                              tvals, H)
        stats = lambda x: x[:H * nb * SUBLANES * LANES].reshape(
            H * nb * SUBLANES, LANES)
        res = (head_split(q2, H), head_split(kx2, H), head_split(vfx2, H),
               lgf[:H * C * V * K].reshape(H, C, V, K), stats(rmf),
               stats(rsf))
        grads = gat_backward(st, _uncov(st, H, pcsr), st_t, fidx, tidx, res,
                             head_split(do2, H), geom=pcsr, geom_t=pcsr_t,
                             interpret=interpret, slope=slope)
        return tuple(head_merge(x) for x in grads)
    return branch


# ------------------------------------------------------------- builder
def build_dist_gat(g, *, slope: float, H: int):
    """Build the distributed (multi-head) GAT message closure for one
    DistGraph: ``f(Q, K, Vf) -> (H, n, d)`` over ``(H, n, d)`` stacks in
    the merged mesh layout handled by ``DistGraph.gat_message``.

    Engine backend → one natively-differentiable SPMD program.  Pallas
    backend → ``custom_vjp``: two kernels per shard forward, all-Pallas
    flash-recompute backward with halo gradient scatter-back."""
    rows_pad = g.part.rows_pad
    mesh = g.mesh
    pallas = g.backend == "pallas"

    def exchange(k2, vf2, sidx, hsrc):
        """Joint K/Vf halo exchange: one all_gather serves both operands
        of the shard's SDDMM + SpMM, every head included."""
        dk = k2.shape[1]
        halo = halo_exchange(jnp.concatenate([k2, vf2], axis=1),
                             sidx, hsrc, axis_name=AXIS)
        return (jnp.concatenate([k2, halo[:, :dk]], axis=0),
                jnp.concatenate([vf2, halo[:, dk:]], axis=0))

    pack = g.gat_pack(H)
    fwd_branches = [
        _fwd_branch(p, H=H, slope=slope, backend=g.backend,
                    interpret=g.interpret, logits_pad=pack.logits_pad,
                    stats_pad=pack.stats_pad)
        for p in pack.fwd.pcsrs]

    def fwd_body(q2, k2, vf2, colidx, lrow, trow, init, fini, vals,
                 sidx, hsrc):
        kx, vfx = exchange(k2, vf2, sidx[0], hsrc[0])
        i = jax.lax.axis_index(AXIS)
        return jax.lax.switch(i, fwd_branches, colidx[0], lrow[0],
                              trow[0], init[0], fini[0], vals[0],
                              q2, kx, vfx)

    fwd_sm = shard_map_2d(fwd_body, mesh, 11, n_out=4 if pallas else 1)

    @jax.jit
    def run_fwd(Q, K, Vf):
        out = fwd_sm(g.pad_heads(Q), g.pad_heads(K), g.pad_heads(Vf),
                     *pack.fwd.arrays, g._send_idx, g._halo_src)
        if not pallas:
            return g.unpad_heads(out, H)
        out2, lg, rm, rs = out
        return g.unpad_heads(out2, H), lg, rm, rs

    if not pallas:
        return run_fwd             # natively differentiable

    state = {}                 # the backward program, built on first use

    def get_bwd():
        if "fn" in state:
            return state["fn"]
        ensure_gat_bwd_pack(pack)
        branches = [_bwd_branch(p, pt, H=H, slope=slope,
                                interpret=g.interpret)
                    for p, pt in zip(pack.fwd.pcsrs, pack.bwd.pcsrs)]
        n_parts, max_send = g.halo.n_parts, g.halo.max_send

        def bwd_body(do2, q2, k2, vf2, fc, fl, ft, fi_, ff, fv,
                     tc, tl, tt, ti, tf_, tv, fidx, tidx, lg, rm, rs,
                     sidx, hsrc):
            # flash-style recompute: re-exchange the K/Vf halo instead of
            # holding the extended operands as residuals
            kx, vfx = exchange(k2, vf2, sidx[0], hsrc[0])
            i = jax.lax.axis_index(AXIS)
            dq2, dkx2, dvfx2 = jax.lax.switch(
                i, branches, fc[0], fl[0], ft[0], fi_[0], ff[0], fv[0],
                tc[0], tl[0], tt[0], ti[0], tf_[0], tv[0],
                fidx[0], tidx[0], do2, q2, kx, vfx, lg[0], rm[0], rs[0])
            # joint halo gradient scatter-back (dK and dVf in one
            # collective), the exact transpose of the forward exchange
            dhalo = jnp.concatenate([dkx2[rows_pad:], dvfx2[rows_pad:]],
                                    axis=1)
            back = halo_scatter_back(dhalo, sidx[0], hsrc[0],
                                     n_parts=n_parts, max_send=max_send,
                                     rows_pad=rows_pad, axis_name=AXIS)
            wk = dkx2.shape[1]
            return (dq2, dkx2[:rows_pad] + back[:, :wk],
                    dvfx2[:rows_pad] + back[:, wk:])

        sm = shard_map_2d(bwd_body, mesh, 23, n_out=3)

        @jax.jit
        def run_bwd(Q, K, Vf, lg, rm, rs, dOut):
            dq2, dk2, dvf2 = sm(g.pad_heads(dOut), g.pad_heads(Q),
                                g.pad_heads(K), g.pad_heads(Vf),
                                *pack.fwd.arrays, *pack.bwd.arrays,
                                pack.f_idx, pack.t_idx, lg, rm, rs,
                                g._send_idx, g._halo_src)
            return tuple(g.unpad_heads(x, H) for x in (dq2, dk2, dvf2))

        state["fn"] = run_bwd
        return run_bwd

    @jax.custom_vjp
    def f(Q, K, Vf):
        return run_fwd(Q, K, Vf)[0]

    def f_fwd(Q, K, Vf):
        out, lg, rm, rs = run_fwd(Q, K, Vf)
        return out, (Q, K, Vf, lg, rm, rs)

    def f_bwd(res, dOut):
        Q, K, Vf, lg, rm, rs = res
        return get_bwd()(Q, K, Vf, lg, rm, rs, dOut)

    f.defvjp(f_fwd, f_bwd)
    return f
