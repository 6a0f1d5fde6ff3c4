"""Share of chip 0's busy time in the round's server side: ops under
``fed/aggregate`` (weighting, the aggregator, round metrics;
``benchmark/scope_reduce.py``)."""

from benchmark import scope_reduce


def read(ctx):
    return scope_reduce.phase_pct(ctx, "aggregate")
