"""Share of chip 0's busy time in backward ops of local training: ops under
``fed/fwd_bwd`` that jax marked ``transpose(`` (``benchmark/scope_reduce.py``)."""

from benchmark import scope_reduce


def read(ctx):
    return scope_reduce.phase_pct(ctx, "train_bwd")
