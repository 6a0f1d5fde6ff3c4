"""Share of chip 0's busy time building batch stacks from the on-device
dataset: ops under ``fed/gather``, in the round and in the eval gather
program (``benchmark/scope_reduce.py``)."""

from benchmark import scope_reduce


def read(ctx):
    return scope_reduce.phase_pct(ctx, "gather")
