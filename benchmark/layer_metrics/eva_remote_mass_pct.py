"""The share of a query's softmax that lies on the chunk summaries, mean over
the queries past the first window and over the layers (the engine's
``eva/remote_mass/layer_<i>`` counters, last round of the traced window): how
much of the attention the linearised part carries at these weights."""

from benchmark import eva_reduce


def read(ctx):
    return eva_reduce.remote_mass_pct()
