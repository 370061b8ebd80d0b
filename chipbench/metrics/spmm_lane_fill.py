"""Kernels: the share of the lanes the ``paramspmm_*`` kernels gather that
carry a column of their dense operand, from the program's counter
``kernel_gather_lanes_total{kernel, lanes="moved"|"carried"}`` (added to
as each kernel form is traced), read in a traced run in which those
kernels ran on the device; ``None`` where the program keeps no such
counter."""
from chipbench import trace


def lane_fill(series: dict) -> float | None:
    """Carried over moved lanes of the ``paramspmm_*`` kernels, in %, from
    the counter's series ``{"kernel=<name>,lanes=<kind>": lanes}``."""
    lanes = {"moved": 0.0, "carried": 0.0}
    for key, value in series.items():
        labels = dict(kv.split("=", 1) for kv in key.split(","))
        if (labels.get("kernel", "").startswith("paramspmm_")
                and labels.get("lanes") in lanes):
            lanes[labels["lanes"]] += value
    return (100.0 * lanes["carried"] / lanes["moved"] if lanes["moved"]
            else None)


def read(ctx):
    red = ctx.get("trace")
    if not red or trace.seconds_by_prefix(red["op_seconds"],
                                          "paramspmm_") <= 0:
        return None
    from repro.obs import metrics_snapshot
    return lane_fill(metrics_snapshot().get("kernel_gather_lanes_total", {}))
