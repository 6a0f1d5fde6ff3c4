"""Assignments that land on held experts over tokens x experts a token, mean of
the layers (the program's ``moe/assignments_held`` counters); 25 when
routing is even over a quarter of the experts."""

from benchmark import moe_reduce


def read(ctx):
    return moe_reduce.held_share_pct(ctx)
