"""Assignments that land on held experts over tokens x experts a token, mean of
the routed blocks (the program's ``moe/assignments_held`` counters); 3.125
when routing is even over 8 of 256 experts."""

from benchmark import mla_reduce


def read(ctx):
    return mla_reduce.routed_held_pct(ctx)
