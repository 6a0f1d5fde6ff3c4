"""``moe_routed_time_pct`` in the cell whose mixer is sparse attention: busy
share of ops under the routed experts' four ``moe/*`` scopes (the accepted
reader's arithmetic under the cell's name; PERF.md section 7)."""

from benchmark import moe_reduce


def read(ctx):
    return moe_reduce.scope_pct(ctx, "route|dispatch|experts|combine")
