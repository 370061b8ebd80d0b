"""``DynamicGraph`` — the operator-facing dynamic-graph surface.

Owns a :class:`~repro.dynamic.pcsr.DynamicPCSR`, a
:class:`~repro.dynamic.governor.RepackGovernor`, and the jitted operator
closures built over the current layout view.  Every mutation batch runs
the governor; with ``auto_heal=True`` (the default) its verdict is acted
on immediately — ``reselect`` swaps the F tile on the live arrays,
``repack`` rebuilds the steering pack under a fresh config pick — so a
caller streaming edges never has to schedule maintenance itself, yet
every SpMM/GAT call stays exact (the view always encodes the live edge
set; only the layout's *speed* was ever at stake).

Operator closures (engine and Pallas alike) capture steering arrays and
masks at build time, so they are rebuilt lazily whenever
``DynamicPCSR.version`` moves — the price of a mutation batch is one
re-trace on the next call, not a stale result.
"""
from __future__ import annotations

from typing import Optional

from repro.core import CostModel, CSRMatrix, SpMMConfig, config_space
from repro.core.engine import make_gat_message_fn, make_spmm_fn
from repro.obs import trace as _obs_trace
from repro.kernels import resolve_backend

from .governor import GovernorDecision, RepackGovernor
from .pcsr import DynamicPCSR, MutationReport


class DynamicGraph:
    """A mutable graph with always-exact, self-healing SpMM/GAT.

    ``backend`` is ``"engine"`` (pure JAX) or ``"pallas"`` (``None``:
    chosen by the platform, ``repro.kernels.resolve_backend``);
    ``auto_heal=False`` keeps the governor advisory-only (its decisions
    still append to ``self.decisions``) so a caller can batch re-packs
    at its own cadence via ``repack()``.
    """

    def __init__(self, csr: CSRMatrix, dim: int, *,
                 config: Optional[SpMMConfig] = None,
                 backend: str | None = None, interpret: bool | None = None,
                 heads: int = 1, space=None, calibration=None,
                 slack: float = 1.25, amortize_steps: int = 100,
                 drift_threshold=None, auto_heal: bool = True):
        self.dim = dim
        self.backend, self.interpret = resolve_backend(backend, interpret)
        self.heads = heads
        self.space = space or config_space(dim)
        self.calibration = calibration
        if config is None:
            config, _ = CostModel(csr, calibration=calibration).best(
                dim, self.space, H=heads)
        self.dyn = DynamicPCSR.from_csr(csr, config)
        self.governor = RepackGovernor(
            dim, heads=heads, space=self.space, calibration=calibration,
            slack=slack, amortize_steps=amortize_steps,
            drift_threshold=drift_threshold)
        self.governor.rebaseline(self.dyn, config)
        self.auto_heal = auto_heal
        self.decisions: list[GovernorDecision] = []
        self._fn_version = -1
        self._spmm_fn = None
        self._gat_fns: dict = {}

    @property
    def config(self) -> SpMMConfig:
        return self.dyn.config

    @property
    def version(self) -> int:
        return self.dyn.version

    # -------------------------------------------------------- mutation
    def insert_edges(self, rows, cols, values
                     ) -> tuple[MutationReport, GovernorDecision]:
        rep = self.dyn.insert_edges(rows, cols, values)
        return rep, self._govern()

    def delete_edges(self, rows, cols
                     ) -> tuple[MutationReport, GovernorDecision]:
        rep = self.dyn.delete_edges(rows, cols)
        return rep, self._govern()

    def _govern(self) -> GovernorDecision:
        dec = self.governor.evaluate(self.dyn, self.config)
        if self.auto_heal:
            if dec.action == "repack":
                self.repack(dec.config)
            elif dec.action == "reselect":
                self.dyn.reselect(dec.config)
        self.decisions.append(dec)
        return dec

    def repack(self, config: Optional[SpMMConfig] = None) -> SpMMConfig:
        """Full re-pack of the live edge set; ``config=None`` re-runs the
        config pick (decider re-pick) on the mutated graph."""
        if config is None:
            config, _ = CostModel(self.dyn.to_csr(),
                                  calibration=self.calibration).best(
                self.dim, self.space, H=self.heads)
        with _obs_trace.span("dynamic.repack",
                             config=str(config.astuple()),
                             nnz=int(self.dyn.nnz)):
            self.dyn.repack(config)
        self.governor.rebaseline(self.dyn, config)
        return config

    # -------------------------------------------------------- operators
    def _refresh(self) -> None:
        if self._fn_version != self.dyn.version:
            self._spmm_fn = None
            self._gat_fns = {}
            self._fn_version = self.dyn.version

    def spmm(self, B):
        """C = A·B over the live (possibly degraded) layout — exact."""
        self._refresh()
        if self._spmm_fn is None:
            self._spmm_fn = make_spmm_fn(self.dyn.pcsr,
                                         backend=self.backend,
                                         interpret=self.interpret)
        return self._spmm_fn(B)

    def gat(self, Q, K_mat, Vf, *, slope: float = 0.2):
        """Fused GAT message over the live layout — exact (tombstoned
        slots are masked, delta-chunk padding carries −inf logits)."""
        self._refresh()
        if slope not in self._gat_fns:
            self._gat_fns[slope] = make_gat_message_fn(
                self.dyn.pcsr, backend=self.backend,
                interpret=self.interpret, slope=slope)
        return self._gat_fns[slope](Q, K_mat, Vf)
