"""Share of chip 0's busy time in ops under the shared expert's scope
``moe/shared``, forward, recomputed forward and backward."""

from benchmark import moe_reduce


def read(ctx):
    return moe_reduce.scope_pct(ctx, "shared")
