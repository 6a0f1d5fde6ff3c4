"""The worst layer's most loaded held expert over the mean held expert's
load (the program's ``moe/load_max_over_mean`` counters): 1 when routing is
even, ``outputs / k`` when every token chooses the same experts."""

from benchmark import moe_reduce


def read(ctx):
    return max(moe_reduce.per_layer("moe/load_max_over_mean"), default=None)
