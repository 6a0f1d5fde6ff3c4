"""Share of chip 0's busy time in ops under the routed-expert layer's scopes
(``moe/route``, ``moe/dispatch``, ``moe/experts``, ``moe/combine``), forward
and backward."""

from benchmark import moe_reduce


def read(ctx):
    return moe_reduce.scope_pct(ctx, "route|dispatch|experts|combine")
