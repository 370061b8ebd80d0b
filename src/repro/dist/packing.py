"""Mesh plumbing shared by the distributed operators.

Two pieces every SPMD program in ``repro.dist`` is built from:

* ``shard_map_2d`` — the one ``jax.shard_map`` wrapper: every operand is
  a rank-2 array sharded along the leading ``("parts",)`` axis unless
  listed in ``replicated`` (read whole by every shard, e.g. a
  per-feature bias row) and every output is sharded the same way unless
  an explicit ``out_specs`` says otherwise (the fused backward returns a
  *replicated* ``dbias`` produced by an in-program ``psum``).
* ``pack_shards`` — per-shard *covered* PCSR steering arrays
  (``PCSR.steering(H, covered=True)``) padded to uniform shapes and
  stacked along a leading partition axis, so one mesh-sharded tensor
  carries every shard's (different-config!) steering data.  ``H > 1``
  packs the head-tiled arrays: per head the real chunks come first and
  the coverage chunks last, so a branch can recover the *uncovered*
  arrays by reshaping ``(H, C_cov·m)`` and slicing ``[:, :C·m]`` — no
  gather, no second pack (the prefix property the GAT branches rely on).

Config heterogeneity is per *shard*: each partition's cost model may
pick a different ⟨W, F, V, S⟩ — including the balanced ``B`` chunk
schedule for degree-skewed partitions — and the pack only ever sees the
resulting steering arrays (padded to the max C·K across shards), so
balanced and uniform shards coexist in one mesh tensor with no special
casing here.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import PartitionSpec

AXIS = "parts"


def shard_map_2d(f, mesh, n_in: int, replicated: tuple = (),
                 n_out: int = 1, out_specs=None):
    """Wrap ``f`` in a ``shard_map`` over the partition mesh.

    Every argument is sharded ``PartitionSpec("parts", None)`` except the
    ``replicated`` indices (read whole by every shard).  ``n_out > 1``
    shards every output the same way; pass ``out_specs`` explicitly when
    an output is replicated (e.g. a ``psum``-reduced bias gradient).
    """
    spec = PartitionSpec(AXIS, None)
    rspec = PartitionSpec(None, None)
    in_specs = tuple(rspec if i in replicated else spec
                     for i in range(n_in))
    if out_specs is None:
        out_specs = spec if n_out == 1 else (spec,) * n_out
    # compiled Pallas branches declare no per-axis variance (vma) on
    # their outputs, which shard_map's checker would reject
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


@dataclass
class PackedShards:
    """Per-shard *covered* PCSR steering arrays (every block visited —
    ``PCSR.steering(covered=True)``) padded to uniform shapes and stacked
    along a leading partition axis (device arrays).  Coverage chunks come
    after the real ones *within each head's segment*, so an engine branch
    slicing the uncovered prefix and a Pallas branch slicing the covered
    length read the same pack."""

    pcsrs: list                  # per-shard PCSR (host; static shapes)
    colidx: jnp.ndarray          # (P, S_max) int32
    lrow: jnp.ndarray            # (P, S_max) int32
    trow: jnp.ndarray            # (P, C_max) int32
    init: jnp.ndarray            # (P, C_max) int32
    fini: jnp.ndarray            # (P, C_max) int32 — last chunk of block
    vals: jnp.ndarray            # (P, VS_max) float32, flattened (C,V,K)

    @property
    def arrays(self) -> tuple:
        """The six mesh-sharded steering operands, in the branch-argument
        order every SPMD body uses."""
        return (self.colidx, self.lrow, self.trow, self.init, self.fini,
                self.vals)


def shard_steering(pcsr, colidx, lrow, trow, init, fini, vals, H: int = 1):
    """One shard's covered (``H``-head-tiled) steering dict, cut from its
    row of a pack (padded to the widest shard) inside a branch: the
    operand ``core.engine``'s operators take."""
    S, C = H * pcsr.covered_num_chunks * pcsr.K, H * pcsr.covered_num_chunks
    return {"colidx": colidx[:S], "lrow": lrow[:S], "trow": trow[:C],
            "init": init[:C], "fini": fini[:C],
            "vals": vals[:C * pcsr.config.V * pcsr.K].reshape(
                C, pcsr.config.V, pcsr.K)}


def pack_shards(pcsrs, H: int = 1) -> PackedShards:
    """Stack the shards' covered (optionally ``H``-head-tiled) steering
    arrays into mesh-shardable tensors, zero-padded to the maxima."""
    P = len(pcsrs)
    sts = [p.steering(H, covered=True) for p in pcsrs]
    S = max(s["colidx"].shape[0] for s in sts)
    C = max(s["trow"].shape[0] for s in sts)
    VS = max(s["vals"].size for s in sts)
    colidx = np.zeros((P, S), np.int32)
    lrow = np.zeros((P, S), np.int32)
    trow = np.zeros((P, C), np.int32)
    init = np.zeros((P, C), np.int32)
    fini = np.zeros((P, C), np.int32)
    vals = np.zeros((P, VS), np.float32)
    for i, s in enumerate(sts):
        colidx[i, :s["colidx"].shape[0]] = s["colidx"]
        lrow[i, :s["lrow"].shape[0]] = s["lrow"]
        trow[i, :s["trow"].shape[0]] = s["trow"]
        init[i, :s["init"].shape[0]] = s["init"]
        fini[i, :s["fini"].shape[0]] = s["fini"]
        vals[i, :s["vals"].size] = s["vals"].reshape(-1)
    # packs are built lazily — sometimes inside a backward trace — and
    # cached on the DistGraph; force concrete (non-tracer) device arrays
    # so the cache is safe to reuse across traces
    with jax.ensure_compile_time_eval():
        return PackedShards(list(pcsrs), *map(jnp.asarray,
                                              (colidx, lrow, trow, init,
                                               fini, vals)))
