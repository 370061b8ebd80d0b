"""Host set-up: seconds in which the program was compiling or loading
compiled code before the traced window (its compile records that end
before the profile starts, nested phases counted once), read from
``repro.obs.host_stalls()``."""
from chipbench import stalls


def read(ctx):
    got = stalls.on_trace(ctx)
    return None if got is None else stalls.compile_seconds(got[1])
