"""Share of chip 0's busy time in the LM head and the loss, forward and
backward: ops of local training under the ``head`` module or ``fed/loss``
(a part of the forward and backward shares; ``benchmark/scope_reduce.py``)."""

from benchmark import scope_reduce


def read(ctx):
    return scope_reduce.phase_pct(ctx, "head_loss")
