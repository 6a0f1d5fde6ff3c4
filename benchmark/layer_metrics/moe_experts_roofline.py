"""The held experts' grouped products' share of their roofline: the least time
for the assignments the program counted (``benchmark/moe_costs.py``) over
the device time under ``moe/experts``."""

from benchmark import moe_reduce


def read(ctx):
    return moe_reduce.experts_roofline_pct(ctx)
