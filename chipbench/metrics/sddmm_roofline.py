"""Kernels: the least time the step's attention scores need (the sampled
products q·k and dOut·v, larger of FLOPs over peak and compulsory bytes
over HBM bandwidth, per product), over the device time of the ``sddmm*``
kernels per traced step."""
from chipbench import trace, work


def read(ctx):
    red, costs = ctx.get("trace"), (ctx.get("work") or {}).get("sddmm")
    if not red or not costs:
        return None
    spent = trace.seconds_by_prefix(red["op_seconds"], "sddmm")
    if spent <= 0:
        return None
    least = work.least_seconds(costs, ctx["peaks"]) * ctx["steps"]
    return 100.0 * least / spent
