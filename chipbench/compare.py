"""The comparisons that decide ``correct``, each a number held against
its limit from ``limits/<cell>.json`` (readings and limits: PERF.md)."""
from __future__ import annotations

import numpy as np

from chipbench.harness import check

# a leaf whose reference gradient is under this share of the median
# leaf's moves under Adam by round-off alone: left out of the change
QUIET_LEAF = 1e-3


def norm_gaps(prog: list, ref: list, keep=None) -> float:
    """Worst leaf's |‖p‖ − ‖r‖| over the larger of ‖r‖ and the median
    leaf's ‖r‖ (the gap of norms, not the norm of the difference)."""
    p, r = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    if keep is not None:
        p, r = p[keep], r[keep]
    scale = np.maximum(r, np.median(r))
    return float(np.max(np.abs(p - r) / scale))


def train_checks(prog: dict, ref: dict, limits: dict) -> dict:
    """The numbers a training cell compares, those its limits name:

    * ``loss_gap``: worst checked step's relative loss gap; ``loss1_gap``:
      the first step's alone;
    * ``grad_gap``: norm gaps of the first gradient, leaf by leaf;
    * ``update_gap``: norm gaps of the parameters' change over the
      checked steps; ``update1_gap``: over the first step alone.

    Changes leave out leaves whose reference gradient is under
    ``QUIET_LEAF`` of the median leaf's."""
    pl, rl = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    g = np.asarray(ref["grad_norms"])
    keep = g >= QUIET_LEAF * np.median(g)
    values = {
        "loss_gap": float(np.max(np.abs(pl - rl) / np.abs(rl))),
        "loss1_gap": float(abs(pl[0] - rl[0]) / abs(rl[0])),
        "grad_gap": norm_gaps(prog["grad_norms"], ref["grad_norms"]),
        "update_gap": norm_gaps(prog["delta_norms"], ref["delta_norms"],
                                keep),
        "update1_gap": norm_gaps(prog["delta1_norms"], ref["delta1_norms"],
                                 keep),
    }
    return {k: check(values[k], limits[k]) for k in values if k in limits}

