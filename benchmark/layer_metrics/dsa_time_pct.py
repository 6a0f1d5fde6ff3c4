"""Share of chip 0's busy time under the scope ``attn/dsa``: the
sparse-attention mixer whole (the attention's four projections, norms and
rotation, the indexer, the selection, the masked flash kernels and the index
loss), forward, recomputed forward and backward."""

from benchmark import mla_reduce


def read(ctx):
    return mla_reduce.scope_pct(ctx, "attn/dsa")
