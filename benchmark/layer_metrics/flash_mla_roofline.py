"""The three flash kernels' share of their roofline at two head widths over the
traced rounds: the least time of every block's forward and backward call
(``benchmark/mla_costs.py``: 192 score and 128 value columns, the shared
rotary key moved once) over the summed self time of ``flash_fwd``,
``flash_bwd_dkv`` and ``flash_bwd_dq``."""

from benchmark import mla_reduce


def read(ctx):
    return mla_reduce.flash_mla_roofline_pct(ctx)
