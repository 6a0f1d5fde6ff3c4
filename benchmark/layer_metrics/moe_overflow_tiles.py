"""Row tiles of one expert that the routed layers computed past their
buffers' capacity, all routed blocks together, in the last round of the
traced window (the program's ``moe/overflow_tiles`` counters): 0 where every
held assignment had a row in the buffers."""

from benchmark import moe_reduce


def read(ctx):
    tiles = moe_reduce.per_layer("moe/overflow_tiles")
    return sum(tiles) if tiles else None
