"""Share of chip 0's busy time under the scope ``mlp/dense``: the dense gated
feed-forward's three products and its gate, forward, recomputed forward and
backward, in the model whose every layer is dense."""

from benchmark import mla_reduce


def read(ctx):
    return mla_reduce.scope_pct(ctx, "mlp/dense")
