"""Share of chip 0's busy time in ops that no ``fed/*`` phase claims and whose
innermost loop is the local-step scan (``loop/steps``, with ``loop/epochs``
around it): its carry's copies, the slice of a step's batch, the key split.
None for a program without the loops' names (``benchmark/loop_reduce.py``)."""

from benchmark import loop_reduce


def read(ctx):
    return loop_reduce.loop_pct(ctx, "steps")
