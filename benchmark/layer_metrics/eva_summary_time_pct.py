"""The part of ``eva_time_pct`` under the scope ``attn/eva/summary``: the
chunk summaries of the keys and values (the weights under phi, the two pools,
mu) and their backward."""

from benchmark import mla_reduce


def read(ctx):
    return mla_reduce.scope_pct(ctx, "attn/eva/summary")
