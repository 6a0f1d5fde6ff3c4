"""The attention custom calls' share of their roofline under a selected set
over the traced rounds: the least time of every layer's call, forward and
backward, by its *selected* pairs (``benchmark/dsa_costs.py``
``attention_cost``: ``moe_costs.attention_cost``'s rule, 4 x pairs x d forward
and 10 x backward, K and V moved once a KV head, the selection's bytes once a
call) over the summed self time of ``flash_fwd.*`` and ``flash_bwd_dkv.*``. A
kernel that visits every causal tile under the set's bits reads low: the gap
is what skipping and gathering could win. A program without the kernels or the
``dsa/call`` notes gives None."""

from benchmark import dsa_reduce


def read(ctx):
    return dsa_reduce.flash_roofline_pct(ctx)
