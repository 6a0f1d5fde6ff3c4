"""Share of chip 0's busy time in pooled eval: ops under ``fed/eval``; 0.0 where
the traced rounds run none (``benchmark/scope_reduce.py``)."""

from benchmark import scope_reduce


def read(ctx):
    return scope_reduce.phase_pct(ctx, "eval")
