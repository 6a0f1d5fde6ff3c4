"""The flash kernels' share of their roofline under EVA attention over the
traced rounds: the least time of every layer's local call (the windows'
causal squares) and remote call (the staircase over the summaries), forward
and backward, by their visible pairs (``benchmark/eva_costs.py``
``attention_cost``: ``moe_costs.attention_cost``'s rule, 4 x pairs x d forward
and 10 x backward) over the summed self time of ``flash_fwd.*`` and
``flash_bwd_dkv.*`` (the eval's forward calls count in the denominator, as in
``flash_window_roofline``). A program without the kernels or the ``eva/call``
notes gives None."""

from benchmark import eva_reduce


def read(ctx):
    return eva_reduce.flash_roofline_pct(ctx)
