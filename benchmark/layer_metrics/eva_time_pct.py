"""Share of chip 0's busy time under the scope ``attn/eva``: the EVA mixer
whole (q, k, v and output projections, the rotation, the chunk summaries, the
two flash calls and their merge), forward, recomputed forward and backward."""

from benchmark import mla_reduce


def read(ctx):
    return mla_reduce.scope_pct(ctx, "attn/eva")
