"""Share of chip 0's busy time under ``attn/dsa/index``: the indexer's three
projections, its norm and rotation, forward and backward, and the index scores
made for the selection (``attn/dsa/index/scores``)."""

from benchmark import mla_reduce


def read(ctx):
    return mla_reduce.scope_pct(ctx, "attn/dsa/index")
