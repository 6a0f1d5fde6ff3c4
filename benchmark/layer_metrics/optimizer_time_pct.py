"""Share of chip 0's busy time in the optimizer's passes: ops under ``fed/opt``
(the update, its application and the empty-batch guards; ``benchmark/scope_reduce.py``)."""

from benchmark import scope_reduce


def read(ctx):
    return scope_reduce.phase_pct(ctx, "optimizer")
