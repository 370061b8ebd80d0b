"""Kernels: the least time the step's SpMM aggregations need (larger of
FLOPs over peak and compulsory bytes over HBM bandwidth, per product),
over the device time of the ``paramspmm_*`` kernels per traced step."""
from chipbench import trace, work


def read(ctx):
    red, costs = ctx.get("trace"), (ctx.get("work") or {}).get("spmm")
    if not red or not costs:
        return None
    spent = trace.seconds_by_prefix(red["op_seconds"], "paramspmm_")
    if spent <= 0:
        return None
    least = work.least_seconds(costs, ctx["peaks"]) * ctx["steps"]
    return 100.0 * least / spent
