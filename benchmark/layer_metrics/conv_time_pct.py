"""Share of chip 0's busy time that MXU operations took in the traced rounds
(convolutions are what a ResNet's rounds run); the rule is ``benchmark/trace_reduce.py``'s."""

from benchmark import trace_reduce


def read(ctx):
    return trace_reduce.mxu_share_pct(ctx["trace"]["chip0"])
