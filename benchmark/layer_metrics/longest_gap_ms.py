"""The longest stretch of the traced window with no operation on chip 0."""


def read(ctx):
    gaps = ctx["trace"]["chip0"]["gaps"]
    return 1e3 * max(d for _, d in gaps) if gaps else 0.0
