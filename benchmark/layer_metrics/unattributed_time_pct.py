"""Share of chip 0's busy time in ops with no ``fed/*`` phase scope, or with no
row in the scope table at all: the attribution's own health. 100 where the
trace gives no scopes (``benchmark/scope_reduce.py``)."""

from benchmark import scope_reduce


def read(ctx):
    return scope_reduce.phase_pct(ctx, "unattributed")
