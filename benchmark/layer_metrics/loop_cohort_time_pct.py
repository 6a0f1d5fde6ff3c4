"""Share of chip 0's busy time in ops that no ``fed/*`` phase claims and whose
innermost loop is the cohort's execution (``loop/cohort``: ``lax.map`` over
the clients, or their ``vmap``): stacking a client's result into ``[C, ...]``,
broadcasting the global variables. None for a program without the loops'
names (``benchmark/loop_reduce.py``)."""

from benchmark import loop_reduce


def read(ctx):
    return loop_reduce.loop_pct(ctx, "cohort")
