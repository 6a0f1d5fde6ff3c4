"""Share of chip 0's busy time under ``attn/dsa/index_loss``: the index loss's
own pass over the index scores, its pass over q k^T for the heads' mean
distribution, the KL and the three gradients, and the backward's scaling."""

from benchmark import mla_reduce


def read(ctx):
    return mla_reduce.scope_pct(ctx, "attn/dsa/index_loss")
