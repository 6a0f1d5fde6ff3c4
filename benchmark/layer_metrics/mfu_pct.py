"""Model FLOP/s utilisation of the traced run's window: the family's FLOPs a
round (forward and backward of real, unpadded examples; no recompute, no
eval) times rounds a second, over chips times the bf16 peak. An end-to-end
utilisation, not a kernel's roofline share."""


def read(ctx):
    peak = ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"]
    return 100.0 * ctx["flops_per_round"] * ctx["window"]["rounds_per_s"] / peak
