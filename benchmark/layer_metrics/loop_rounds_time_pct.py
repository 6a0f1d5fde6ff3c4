"""Share of chip 0's busy time in ops that no ``fed/*`` phase claims and whose
innermost loop is the scan over a block's rounds (``loop/rounds``), in the
block-dispatched cell. None for a program without the loops' names
(``benchmark/loop_reduce.py``)."""

from benchmark import loop_reduce


def read(ctx):
    return loop_reduce.loop_pct(ctx, "rounds")
