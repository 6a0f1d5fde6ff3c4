"""The most negative log-decay one chunk cumulates, least of the
delta-attention layers (the program's ``kda/decay_floor/layer_<i>`` counters,
last round of the traced window): far under 0 it says that the cell runs the
regime the chunked form's sub-block exponents exist for (``exp`` of minus it
alone would overflow float32 past 88)."""

from benchmark import moe_reduce


def read(ctx):
    floors = moe_reduce.per_layer("kda/decay_floor")
    return min(floors) if floors else None
