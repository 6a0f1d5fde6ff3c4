"""Tiles of 512 x 512 that hold a chosen (query, key) pair over the causal
tiles, mean over the layers (the engine's ``dsa/tiles_nonempty/layer_<i>``
counters, last round of the traced window): what the masked kernels visit of
what a causal kernel would; the rest they skip by the tile's count."""

from benchmark import dsa_reduce


def read(ctx):
    share = dsa_reduce.counter_mean("tiles_nonempty")
    return None if share is None else 100.0 * share
