"""``mla_time_pct`` in the cell whose one softmax layer in five is latent
attention without positions: busy share of ops under ``attn/mla``."""

from benchmark import mla_reduce


def read(ctx):
    return mla_reduce.scope_pct(ctx, "attn/mla")
