"""The ParamSpMM three-phase workflow (paper Fig. 2):
configuration prediction → PCSR generation → computing engine.
"""
from __future__ import annotations

from time import time_ns
from typing import Optional

import numpy as np

from .core import (CostModel, CSRMatrix, SpMMConfig, config_space,
                   extract_features)
from .core.decider import SpMMDecider
from .core.engine import ParamSpMMOperator
from .core.reorder import rabbit_reorder, apply_reorder
from .obs import metrics as _obs_metrics, trace as _obs_trace


def pick_config(csr: CSRMatrix, dim: int, *,
                decider: Optional[SpMMDecider] = None,
                select: str = "model",
                op: str = "spmm",
                heads: int = 1) -> SpMMConfig:
    """Phase-1 configuration prediction, shared by every entry point.

    Resolution order: ``decider`` prediction > measured oracle search
    (``select="measured"``) > cost-model sweep over ``config_space``.
    ``ParamSpMM`` uses it per matrix; the serving tier
    (``repro.serve``) calls it once per shape bucket and amortizes the
    pick across every request the bucket ever serves.
    """
    if decider is not None:
        return decider.predict(extract_features(csr), dim)
    if select == "measured":
        # autotune for THIS host (the paper's oracle measures on the
        # deployment GPU; on CPU the TPU model mispredicts)
        from .core.autotune import oracle_search
        return oracle_search(csr, dim, mode="measured", reps=2).best_config
    config, _ = CostModel(csr).best(dim, config_space(dim), op=op, H=heads)
    return config


class ParamSpMM:
    """End-to-end adaptive SpMM for one sparse matrix and embedding dim.

    config resolution order: explicit ``config`` > ``decider`` prediction >
    cost-model oracle search (the fallback when no trained decider is at
    hand — e.g. first-run autotuning).

    ``op`` names the operator the config is chosen for ("spmm", "sddmm",
    or "gat" — the SDDMM+softmax+SpMM attention pair); it steers the
    cost-model search only, since the decider is SpMM-trained (per-operator
    decider labels remain a ROADMAP item).  ``heads`` prices multi-head
    attention's head-tiled grids (per-head dim, H× chunks/blocks), so a
    4-head layer can pick a different ⟨W,F,V,S⟩ than a single-head one.

    The wrapped operator exposes the fusion surface: ``p(B)`` is the plain
    SpMM, ``p.fused(B, scale=, bias=, activation=, residual=)`` the
    epilogue-fused aggregation (one kernel per GCN — or, via the residual
    addend, GIN — layer on the Pallas backend).
    """

    def __init__(self, csr: CSRMatrix, dim: int, *,
                 config: Optional[SpMMConfig] = None,
                 decider: Optional[SpMMDecider] = None,
                 reorder: bool = True,
                 backend: str | None = None,
                 interpret: bool | None = None,
                 build_transpose: bool = True,
                 select: str = "model",
                 op: str = "spmm",
                 heads: int = 1):
        # reorder, configuration pick and both packs: the host set-up an
        # aggregation operator costs, observed with or without a session
        t0 = time_ns()
        with _obs_trace.span("spmm.build", op=op, heads=heads, dim=dim,
                             n_rows=int(csr.n_rows), nnz=int(csr.nnz)):
            self.perm = None
            if reorder:              # paper §4.4: default preprocessing
                perm = rabbit_reorder(csr)
                cand = apply_reorder(csr, perm)
                # keep whichever ordering has better V=2 locality —
                # reordering an already well-ordered graph (e.g.
                # co-citation clones) can only hurt, and the metric is
                # cheap (pcsr_stats)
                from .core import pcsr_stats
                pr_old = pcsr_stats(csr.indptr, csr.indices, csr.n_rows,
                                    csr.n_cols, 2, 4).padding_ratio
                pr_new = pcsr_stats(cand.indptr, cand.indices, cand.n_rows,
                                    cand.n_cols, 2, 4).padding_ratio
                if pr_new <= pr_old:
                    self.perm = perm
                    csr = cand
                else:
                    self.perm = np.arange(csr.n_rows)
            self.csr = csr
            self.dim = dim
            if config is None:
                config = pick_config(csr, dim, decider=decider,
                                     select=select, op=op, heads=heads)
            self.config = config
            self.op = ParamSpMMOperator(csr, config, backend=backend,
                                        interpret=interpret,
                                        build_transpose=build_transpose)
        _obs_metrics.histogram("spmm_build_seconds").observe(
            (time_ns() - t0) * 1e-9, op=op)

    def __call__(self, B):
        return self.op(B)

    def fused(self, B, scale=None, bias=None, activation: str = "none",
              residual=None):
        """Epilogue-fused aggregation:
        act(scale ⊙ (A·B) + bias + residual)."""
        return self.op.fused(B, scale=scale, bias=bias,
                             activation=activation, residual=residual)
