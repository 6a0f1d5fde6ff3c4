"""Host milliseconds a round enqueueing round programs: the
``engine/dispatch`` spans of the window over its rounds."""

from benchmark import scope_reduce


def read(ctx):
    return scope_reduce.span_ms_per_round(ctx, "engine/dispatch")
