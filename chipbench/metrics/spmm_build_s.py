"""Host set-up: seconds the program itself records for building its
aggregation operator, the sum of its ``spmm_build_seconds`` (reorder,
configuration pick and both packs in ``pipeline.ParamSpMM``, then the
attention message's set-up in ``core.engine.make_gat_message_fn``); the
inside twin of ``pack_s``."""
from chipbench import stalls


def read(ctx):
    if stalls.on_trace(ctx) is None:
        return None
    from repro.obs import metrics_snapshot
    series = metrics_snapshot().get("spmm_build_seconds")
    return sum(v["sum"] for v in series.values()) if series else None
