"""Device: the share of the traced train window in which the chip ran
nothing while the host was compiling or collecting garbage (a record of
``repro.obs.host_stalls()`` was open); at most ``device_idle.train``."""
from chipbench import stalls


def read(ctx):
    got = stalls.on_trace(ctx)
    return None if got is None else stalls.idle_stalled_share(*got)
