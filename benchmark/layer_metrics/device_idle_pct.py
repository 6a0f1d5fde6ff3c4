"""Share of the traced window in which no operation ran on chip 0."""


def read(ctx):
    return 100.0 * (1.0 - ctx["trace"]["chip0"]["busy_s"] / ctx["trace"]["window_s"])
