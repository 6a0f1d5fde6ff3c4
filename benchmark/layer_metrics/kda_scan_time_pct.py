"""Share of chip 0's busy time in ops under ``attn/kda/scan``: the chunked
recurrence alone, forward and backward (a part of ``kda_time_pct``)."""

from benchmark import mla_reduce


def read(ctx):
    return mla_reduce.scope_pct(ctx, "attn/kda/scan")
