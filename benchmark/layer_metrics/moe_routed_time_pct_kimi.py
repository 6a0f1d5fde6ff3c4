"""``moe_routed_time_pct`` in the cell whose mixers are delta attention: busy
share of ops under the routed experts' four ``moe/*`` scopes."""

from benchmark import moe_reduce


def read(ctx):
    return moe_reduce.scope_pct(ctx, "route|dispatch|experts|combine")
