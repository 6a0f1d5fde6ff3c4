"""Share of chip 0's busy time in ops under the delta-attention module's
scope ``attn/kda``: its projections, three short convolutions, two low-rank
gates, norms and the chunked scan; forward, recomputed forward and backward."""

from benchmark import mla_reduce


def read(ctx):
    return mla_reduce.scope_pct(ctx, "attn/kda")
