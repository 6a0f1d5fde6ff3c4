"""Share of chip 0's busy time in ops under the short-convolution operator's
scope ``mix/shortconv``: its two projections, the two gates and the taps;
forward, recomputed forward and backward."""

from benchmark import mla_reduce


def read(ctx):
    return mla_reduce.scope_pct(ctx, "mix/shortconv")
