"""Share of chip 0's busy time in ops under the scope ``mtp``: the
multi-token-prediction module's norms and product M, its block, its pass
through the shared head and its loss; forward, recomputed forward and
backward."""

from benchmark import mla_reduce


def read(ctx):
    return mla_reduce.scope_pct(ctx, "mtp")
