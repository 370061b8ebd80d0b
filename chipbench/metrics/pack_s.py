"""Host set-up: seconds the program takes to build the aggregation
operator (reorder, configuration pick, PCSR pack and transpose pack),
timed by the benchmark around ``build_spmm``."""


def read(ctx):
    return ctx.get("pack_s")
