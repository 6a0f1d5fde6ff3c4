"""The three flash kernels' share of their roofline over the traced rounds: the
least time of every layer's forward and backward call, counting only the
pairs its mask shows, over the summed self time of ``flash_fwd``,
``flash_bwd_dkv`` and ``flash_bwd_dq``."""

from benchmark import moe_reduce


def read(ctx):
    return moe_reduce.flash_window_roofline_pct(ctx)
