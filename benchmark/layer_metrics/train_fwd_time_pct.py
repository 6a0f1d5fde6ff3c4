"""Share of chip 0's busy time in forward ops of local training: ops under
``fed/fwd_bwd`` that jax did not mark ``transpose(`` (``benchmark/scope_reduce.py``)."""

from benchmark import scope_reduce


def read(ctx):
    return scope_reduce.phase_pct(ctx, "train_fwd")
