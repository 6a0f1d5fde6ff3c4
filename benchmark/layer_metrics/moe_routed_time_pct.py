"""Share of chip 0's busy time in ops under the routed experts' scopes
(``moe/route``, ``moe/dispatch``, ``moe/experts``, ``moe/combine``), forward,
recomputed forward and backward: ``moe_time_pct``'s twin in the cell whose
configuration keeps a shared expert beside them."""

from benchmark import moe_reduce


def read(ctx):
    return moe_reduce.scope_pct(ctx, "route|dispatch|experts|combine")
