"""Blocks of the selection that took the tie path over the blocks searched,
mean over the layers (the engine's ``dsa/select_tie_blocks/layer_<i>``
counters, last round of the traced window): how often the data-dependent
branch of ``ops/dsa_select.py`` engages (the cell's ``impl`` "flash"; the
plain path counts blocks of 512 rows). A block of 128 query rows takes it
when one of its rows holds more scores equal to its ``topk``-th largest than
it needs, and then searches the cut among them over the position's bits, about
what the threshold's own search costs; 0 where float32 sums of sixteen
weighted ReLUs never tie, 100 if a layer's scores collapsed to one value."""

from benchmark import dsa_reduce


def read(ctx):
    share = dsa_reduce.counter_mean("select_tie_blocks")
    return None if share is None else 100.0 * share
