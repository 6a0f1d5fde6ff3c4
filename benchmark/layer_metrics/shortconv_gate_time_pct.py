"""The part of ``shortconv_time_pct`` under the scope ``mix/shortconv/gate``:
the operator's elementwise chain (``B * z``, the taps, ``C * c``) and its
backward, without the projections either side."""

from benchmark import mla_reduce


def read(ctx):
    return mla_reduce.scope_pct(ctx, "mix/shortconv/gate")
