"""The index loss ``L_I`` of a layer, mean over the layers (the engine's
``dsa/index_kl/layer_<i>`` counters, last round of the traced window): the KL
from the heads' mean attention distribution to the indexer's over the chosen
keys, in nats. A diagnostic of the indexer at these weights, not a lever on
the round rate by itself: it is declared to move ``rounds_per_s`` because a
later tile-skipping or gather kernel pays by it (an indexer that has learned
the attention's distribution concentrates its choices, fewer tiles hold a
chosen key, ``dsa_tiles_nonempty_pct`` falls and the rate rises)."""

from benchmark import dsa_reduce


def read(ctx):
    return dsa_reduce.counter_mean("index_kl")
