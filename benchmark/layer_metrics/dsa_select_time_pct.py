"""Share of chip 0's busy time under ``attn/dsa/select``: each row's
threshold among its index scores, the chosen set's packed bits both ways and
the tiles' counts (made once a training step: the set is kept by name)."""

from benchmark import mla_reduce


def read(ctx):
    return mla_reduce.scope_pct(ctx, "attn/dsa/select")
