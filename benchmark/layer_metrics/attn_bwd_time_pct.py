"""Share of chip 0's busy time in the blockwise XLA backward of attention: ops
under ``attn/blockwise_bwd`` (a part of the backward share;
``benchmark/scope_reduce.py``)."""

from benchmark import scope_reduce


def read(ctx):
    return scope_reduce.phase_pct(ctx, "attn_bwd")
