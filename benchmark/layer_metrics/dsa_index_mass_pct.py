"""The share of ``softmax(I[t, :t + 1])`` that lies on the chosen keys, mean
over the queries past ``topk`` and over the layers (the engine's
``dsa/index_mass/layer_<i>`` counters, last round of the traced window): how
much of its own distribution the indexer keeps at these weights. A diagnostic
for a later tile-skipping or gather kernel, as ``dsa_index_kl`` is: a
concentrated indexer (mass near 100 on few keys) leaves tiles empty, which is
what such a kernel turns into a higher ``rounds_per_s``; no change to this
number alone moves the rate."""

from benchmark import dsa_reduce


def read(ctx):
    share = dsa_reduce.counter_mean("index_mass")
    return None if share is None else 100.0 * share
