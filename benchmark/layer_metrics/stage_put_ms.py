"""Host milliseconds a round shipping staged arrays to the device: the
``engine/stage/put`` spans of the window over its rounds."""

from benchmark import scope_reduce


def read(ctx):
    return scope_reduce.span_ms_per_round(ctx, "engine/stage/put")
