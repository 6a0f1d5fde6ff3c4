"""What the loops leave of ``unattributed_time_pct``: chip 0's busy share in ops
with neither a ``fed/*`` phase nor a ``loop/*`` name, or with no row in the
scope table: the attribution's health. None for a program without the loops'
names (``benchmark/loop_reduce.py``)."""

from benchmark import loop_reduce


def read(ctx):
    return loop_reduce.loop_pct(ctx, "unscoped")
