"""The part of ``moe_time_pct`` under ``moe/dispatch`` and ``moe/combine``: the
sort and the moves between token order and sorted order, forward and
backward."""

from benchmark import moe_reduce


def read(ctx):
    return moe_reduce.scope_pct(ctx, "dispatch|combine")
