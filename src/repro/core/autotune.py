"""Oracle config search: exhaustive timing/pricing of the config space.

Two modes:
  * "model"    — analytical TPU cost model (corpus-scale label source);
  * "measured" — wall-clock of the jit'd JAX engine on this host, with B
    padded to the F-tile so the MAC-job gap is physically paid.  CPU time
    is a proxy (no per-step DMA overhead), used to validate the model's
    ranking on a subset (EXPERIMENTS.md records both).
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import (decisions as _obs_decisions, metrics as _obs_metrics,
                       trace as _obs_trace)

from .cost_model import CostModel
from .pcsr import SpMMConfig, build_pcsr, config_space
from .sparse import CSRMatrix


def time_fn(fn, *args, reps: int = 3, warmup: int = 1) -> float:
    """Median wall-clock seconds of fn(*args) with block_until_ready."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    _obs_metrics.counter("autotune_measurements_total").inc(reps)
    return float(np.median(ts))


@dataclass
class OracleResult:
    times: dict            # config -> seconds
    best_config: SpMMConfig
    best_time: float


def oracle_search(csr: CSRMatrix, dim: int, space=None, mode: str = "model",
                  reps: int = 3, rng_seed: int = 0,
                  cm: CostModel | None = None,
                  op: str = "spmm", H: int = 1,
                  calibration=None) -> OracleResult:
    """Exhaustive search of ``space`` for operator ``op`` ("spmm",
    "sddmm", or "gat" — the SDDMM+softmax+SpMM attention pair, timed or
    priced as the sum of its two passes).

    ``H`` is the head count the labels are collected FOR: multi-head
    layers run the head-tiled grid over the *per-head* dim ``ceil(dim/H)``
    (see ``kernel_cost``), so the optimal config genuinely shifts with H
    — a search pinned at H=1 labels multi-head GAT deciders for the wrong
    problem.  Model mode prices ``cm.time(..., H=H)``; measured mode
    times the engine on the actual head-tiled steering arrays
    (``PCSR.steering(H)``) with per-head-dim operands.

    ``calibration`` (a ``CalibrationResult`` or a path to a saved
    artifact) makes model mode price through fitted-to-hardware
    coefficients instead of the hand-set constants — the label source
    the decider should be trained on once a host has been calibrated.
    Ignored when an explicit ``cm`` is passed (build that cost model
    with the calibration instead) and in measured mode (measured times
    need no pricing).
    """
    if op not in ("spmm", "sddmm", "gat"):
        raise ValueError(op)
    if H < 1:
        raise ValueError(f"H must be ≥ 1, got {H}")
    space = space or config_space(dim)
    with _obs_trace.span("oracle.search", mode=mode, op=op, dim=dim, H=H,
                         n_configs=len(space)):
        times = _oracle_times(csr, dim, space, mode, reps, rng_seed, cm,
                              op, H, calibration)
    best = min(times, key=times.get)
    if _obs_trace.trace_enabled():
        _obs_decisions.record_decision(
            csr, source=f"oracle_{mode}", op=op, dim=dim, heads=H,
            chosen=best, predicted_seconds=times[best],
            candidates=times.items(),
            calibration=cm.calibration if cm is not None else calibration)
    return OracleResult(times, best, times[best])


def _oracle_times(csr, dim, space, mode, reps, rng_seed, cm, op, H,
                  calibration) -> dict:
    times = {}
    if mode == "model":
        if cm is None:
            if calibration is not None and not hasattr(calibration, "price"):
                from .calibrate import CalibrationResult
                calibration = CalibrationResult.load(calibration)
            cm = CostModel(csr, calibration=calibration)
        for cfg in space:
            times[cfg] = cm.time(dim, cfg, op, H=H)
    elif mode == "measured":
        from .engine import steered_sddmm, steered_spmm

        rng = np.random.default_rng(rng_seed)
        d_head = -(-dim // H)
        for cfg in space:
            dim_pad = -(-d_head // cfg.dblk) * cfg.dblk
            B = jnp.asarray(
                rng.standard_normal((H * csr.n_cols, dim_pad)), jnp.float32)
            pcsr = build_pcsr(csr.indptr, csr.indices, csr.data,
                              csr.n_rows, csr.n_cols, cfg)
            st = {k: jnp.asarray(v) for k, v in pcsr.steering(H).items()
                  if k in ("colidx", "lrow", "trow", "vals")}
            # the H head-tiled copies traversed as one matrix
            nb = H * pcsr.n_blocks
            geom = SimpleNamespace(config=cfg, n_rows=nb * cfg.R,
                                   n_blocks=nb, num_chunks=H * pcsr.num_chunks,
                                   K=pcsr.K)
            t = 0.0
            if op in ("spmm", "gat"):
                spmm = steered_spmm(st, geom, backend="engine")
                t += time_fn(lambda: spmm(B), reps=reps)
            if op in ("sddmm", "gat"):
                Q = jnp.asarray(
                    rng.standard_normal((H * pcsr.n_blocks * cfg.R, dim_pad)),
                    jnp.float32)
                sddmm = steered_sddmm(st, geom, backend="engine")
                t += time_fn(lambda: sddmm(Q, B), reps=reps)
            times[cfg] = t
    else:
        raise ValueError(mode)
    return times


def throughput_gflops(csr: CSRMatrix, dim: int, seconds: float) -> float:
    """Useful GFLOP/s (2·nnz·dim MACs), the paper's reporting unit."""
    return 2.0 * csr.nnz * dim / seconds / 1e9
