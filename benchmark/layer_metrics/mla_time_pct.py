"""Share of chip 0's busy time in ops under the latent-attention module's
scope ``attn/mla``: its five projections, two norms, rotary arithmetic and
the three flash kernels; forward, recomputed forward and backward, the
multi-token-prediction module's block included."""

from benchmark import mla_reduce


def read(ctx):
    return mla_reduce.scope_pct(ctx, "attn/mla")
