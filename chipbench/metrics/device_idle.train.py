"""Device: the share of the traced train window in which no operation ran
on the chip, 1 − (union of the device's busy intervals) / window."""
from chipbench import trace


def read(ctx):
    return trace.idle_share(ctx.get("trace"))
